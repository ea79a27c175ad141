"""Architecture families of a pool's full-width member, one module each.

A configuration file names its member's family (``"family": "dense_gqa"``),
and :func:`load` imports ``bench.families.<family>``.  Everything that
depends on the architecture lives in that module; the rest of ``bench``
reaches it only through :func:`load`.  A family module defines:

``FIELDS``
    configuration-file key -> ``ModelConfig`` field of the served member.
``main_sizes(cfg, mcfg) -> dict``
    ``cfg`` with its numbers read back from the ``ModelConfig`` actually
    served (the published one, or the program's smoke config).
``sizes(cfg) -> dict``
    the shape numbers of a configuration, by short name.
``shapes(s) -> {leaf: shape}`` and ``scale(leaf, s) -> float | None``
    the weight leaves ``bench.weights`` draws, and the standard deviation
    each is drawn with (None: a norm's gain, which is all ones).
``program_tree(w, mcfg)``
    the drawn leaves laid out as the program's parameter tree.
``logits(cfg, w, tokens, seg, pos, *, lowp=False)``
    the plain float32 reference over a packed row, ``(T, V)``; ``lowp``
    is the control, one precision step below the served one.
``decode_chunk(ctx, lens, remaining, steps) -> (flops, least_bytes)``,
``prefill(ctx, plen) -> flops``, ``step_floor_bytes(ctx) -> bytes``
    the work counts of the roofline and MFU readers; ``ctx`` is the
    reader's ``bench.run.TraceContext`` (``ctx.s`` is ``sizes`` of the
    served member, and the run's records are there for a family whose
    work depends on what a step touched).
"""
from __future__ import annotations

import importlib
import re

_NAME = re.compile(r"[a-z][a-z0-9_]*\Z")


def load(cfg: dict):
    """The family module that ``cfg["family"]`` names.  A configuration
    without one, or naming one that does not exist, is an error."""
    name = cfg.get("family")
    if not isinstance(name, str) or not _NAME.match(name):
        raise ValueError(f"the configuration names no family (got {name!r})")
    module = f"{__name__}.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise ValueError(f"no family {name!r}: bench/families/{name}.py "
                         "does not exist") from None
