"""Paged decode attention on the chip, per layer, two ways: XLA's gather of
each slot's block-table row plus the dense jnp body (the path narrow pages
take), and the paged kernel that reads the live pages in place.

    python chip_decode_attention.py [--blocks 64,128,256] [--out PATH]

Shapes: every endpoint of both benchmark configurations (the full-width
member and the smoke-width stand-ins, at each cell's slots and pages per
slot), then a sweep of page-row widths K·D at the internlm2 cell's slot
geometry, which places the crossover that
``ops.reads_pages_in_place`` encodes.  ``--blocks`` also times the kernel
at other block sizes (in positions) at the members' shapes.

Each way runs every layer of the pool in one jitted ``fori_loop``; the time
is the median over repeats of that call, per layer.  Live lengths follow
the chat mix (prompts lognormal(4.3, 0.5), part of an output of median
~100); ``free`` of a shape's slots hold no request.  Also checks, in bf16
on the chip, the kernel against the gather path, and verify slices
against decode bit for bit at the members' shapes.  Prints one JSON line,
and writes it to ``--out`` where given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

PS = 16
# name -> (slots, query heads, KV heads, head size, pages per slot, layers,
# share of free slots)
MEMBERS = {"danube": (32, 32, 8, 120, 48, 24, 0.0),
           "internlm2": (64, 48, 8, 128, 53, 8, 0.5)}
SHAPES = dict(MEMBERS, **{
    "danube_stand_in": (32, 4, 2, 16, 48, 3, 0.0),
    "internlm2_stand_in": (64, 4, 2, 16, 53, 3, 0.5)})
# page-row widths (K, D) swept at the internlm2 cell's 64 slots x 53 pages
SWEEP = [(2, 16), (2, 32), (2, 64), (8, 16), (4, 64), (2, 128), (8, 64),
         (8, 128)]


def case(shape, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    b, h, kh, d, p, n_layers, free = shape
    rng = np.random.RandomState(seed)
    prompt = np.clip(rng.lognormal(4.3, 0.5, b), 16, 2048)
    out = np.clip(rng.lognormal(np.log(100), 0.6, b), 1, 400)
    lens = np.minimum(prompt + rng.uniform(0, 1, b) * out, p * PS - 1)
    lens = lens.astype(np.int32) + 1          # the step's token is written
    bt = np.zeros((b, p), np.int32)
    perm = rng.permutation(np.arange(1, 1 + b * p))
    for i in range(b):
        if i < int(free * b):
            lens[i] = 1                         # a freed slot: row 0
        else:
            bt[i] = perm[i * p:(i + 1) * p]
    key = jax.random.PRNGKey(seed)
    pool = (n_layers, 1 + b * p, PS, kh, d)
    kp = jax.random.normal(key, pool, jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 1), pool, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (b, 1, h, d),
                          jnp.bfloat16)
    return q, kp, vp, jnp.asarray(bt), jnp.asarray(lens), n_layers


def over_layers(attend, n_layers):
    """A jitted call that runs ``attend(q, kp, vp, bt, lens, i)`` for every
    layer i and sums the outputs (so none is dropped)."""
    import jax
    import jax.numpy as jnp

    def run(q, kp, vp, bt, lens):
        def body(i, acc):
            return acc + attend(q, kp, vp, bt, lens, i).astype(jnp.float32)
        return jax.lax.fori_loop(0, n_layers, body,
                                 jnp.zeros(q.shape, jnp.float32))
    return jax.jit(run)


def timed(fn, args, reps=20):
    import numpy as np
    fn(*args).block_until_ready()
    fn(*args).block_until_ready()
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        fn(*args).block_until_ready()
        ts.append(time.perf_counter() - t)
    return float(np.median(ts))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--blocks", default="",
                    help="more block sizes, in positions, to time the "
                         "kernel at for the members' shapes")
    ap.add_argument("--out", default="", help="also write the JSON line here")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.decode_attention import kernel as K
    from repro.kernels.decode_attention import ops
    from repro.kernels.decode_attention.ref import gather_pages
    from repro.models.attention import decode_attention_jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("chip_decode_attention.py: no TPU")

    layer = lambda x, i: jax.lax.dynamic_index_in_dim(x, i, 0, False)

    def gather(q, kp, vp, bt, lens, i):
        return decode_attention_jnp(q, gather_pages(layer(kp, i), bt),
                                    gather_pages(layer(vp, i), bt), lens)

    def paged(q, kp, vp, bt, lens, i):
        return K.paged_decode_attention_kernel(q, kp, vp, bt, lens, i,
                                               interpret=False)

    def measure(shape, blocks=()):
        q, kp, vp, bt, lens, n_layers = case(shape)
        a = (q, kp, vp, bt, lens)
        b, _, kh, d, p = shape[:5]
        live = int(np.sum(-(-np.asarray(lens) // PS)))
        row = {"shape": dict(zip(("slots", "heads", "kv_heads", "head_dim",
                                  "pages_per_slot", "layers", "free"),
                                 shape)),
               "row_width": kh * d,
               "gather_mb_per_layer": 2 * kp[0, 0].nbytes * b * p / 1e6,
               "live_page_share": live / bt.size,
               "rule_takes_kernel": kh * d >= ops.MIN_KERNEL_ROW}
        ms = lambda t: 1e3 * t / n_layers
        ref = over_layers(gather, n_layers)
        row["gather_ms_per_layer"] = ms(timed(ref, a))
        want = np.asarray(ref(*a))
        fn = over_layers(paged, n_layers)
        row["kernel_ms_per_layer"] = ms(timed(fn, a))
        row["kernel_max_abs_vs_gather"] = float(
            np.max(np.abs(np.asarray(fn(*a)) - want)))
        row["kernel_pays"] = row["kernel_ms_per_layer"] < \
            row["gather_ms_per_layer"]
        default = K.BLOCK_POSITIONS
        for bk in blocks:
            K.BLOCK_POSITIONS = bk
            fn = over_layers(paged, n_layers)
            row[f"kernel_{bk}_ms_per_layer"] = ms(timed(fn, a))
        K.BLOCK_POSITIONS = default
        return row, (q, kp, vp, bt, lens)

    blocks = [int(x) for x in args.blocks.split(",") if x]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "block_positions": K.BLOCK_POSITIONS,
              "min_kernel_row": ops.MIN_KERNEL_ROW}
    for name, shape in SHAPES.items():
        row, (q, kp, vp, bt, lens) = measure(
            shape, blocks if name in MEMBERS else ())
        if name in MEMBERS:
            # verify rows against decode at lens + s, bit for bit, one layer
            s = 4
            qs = jax.random.normal(jax.random.PRNGKey(7), (q.shape[0], s) +
                                   q.shape[2:], jnp.bfloat16)
            lv = jnp.minimum(lens, bt.shape[1] * PS - s)
            ver = jax.jit(lambda *x: K.paged_verify_attention_kernel(
                *x, interpret=False))(qs, kp, vp, bt, lv, 1)
            dec = jax.jit(lambda *x: K.paged_decode_attention_kernel(
                *x, interpret=False))
            row["verify_slices_equal_decode"] = all(
                bool(jnp.array_equal(dec(qs[:, j:j + 1], kp, vp, bt, lv + j,
                                         1)[:, 0], ver[:, j]))
                for j in range(s))
        result[name] = row
        del q, kp, vp
    b, _, _, _, p, _, free = MEMBERS["internlm2"]
    result["sweep"] = [measure((b, 2 * kh, kh, d, p, 3, free))[0]
                       for kh, d in SWEEP]
    line = json.dumps(result)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line)


if __name__ == "__main__":
    main()
