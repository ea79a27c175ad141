"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and its
phase functions — the served path under the sanitizers, the retrieval-vote
kernel-vs-reference check and the paged-vs-dense check — run here at smoke
width, so the script's control flow is covered on every change.  The test
does the steering (smoke configs, small shapes); the script has no CPU
mode."""
import importlib.util
import os

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    out = capsys.readouterr().out
    assert '"ok"' not in out and not out.strip()


def test_chip_smoke_phases_at_smoke_width(chip_smoke):
    from repro.configs import get_smoke_config
    cfgs = [get_smoke_config(a) for a in chip_smoke.POOL_ARCHS]
    server, done, predictor, test = chip_smoke.served_phase(
        cfgs, n_requests=12, prompt_len=33, max_new=4, t_max=48,
        max_concurrency=2, min_full=1)
    assert [r.rid for r in done] == list(range(12))
    assert server.endpoints[0].decoded_tokens == 4 * sum(
        r.endpoint == 0 for r in done)
    diff, nq = chip_smoke.retrieval_check(predictor, test.queries)
    assert nq == 12 and diff <= chip_smoke.VOTE_ATOL
    req = next(r for r in done if r.endpoint == 0)
    agree, steps, gap = chip_smoke.paged_vs_dense(server.endpoints[0], req)
    assert steps == 4 and gap <= chip_smoke.LOGIT_TOL


def test_use_compile_cache_env_wins(monkeypatch, tmp_path):
    from repro.launch.serve import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_use_compile_cache_default_is_fixed_checkout_path(monkeypatch):
    from repro.launch.serve import DEFAULT_CACHE_DIR, use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == DEFAULT_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert DEFAULT_CACHE_DIR == os.path.join(_ROOT, ".jax_cache")
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

