"""The persistent kernel autotuner (PR 10): cache round-trip, default-table
fallback, the small-grid XLA-fallback rule, and the session/PallasOp reads."""

import json

import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (
    DEFAULT_BZ,
    MIN_PALLAS_VOLUME,
    TuneDecision,
    default_decision,
    resolve,
    save_cache,
    tune_key,
)


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    """An isolated cache file: no test reads/writes ~/.cache."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune._CACHE = None
    yield path
    autotune._CACHE = None


# -----------------------------------------------------------------------------
# default table
# -----------------------------------------------------------------------------

def test_default_table_small_grid_falls_back_to_xla():
    """16³ < 24³: the measured case where the fork-join Pallas path ran
    3.5× behind the jitted loop — the default routes it to XLA."""
    dec = default_decision((16, 16, 16), backend="tpu")
    assert dec == TuneDecision(use_pallas=False)
    assert (16 ** 3) < MIN_PALLAS_VOLUME <= (32 ** 3)


def test_default_table_large_grid_uses_pallas_on_tpu_only():
    assert default_decision((64, 64, 64), backend="tpu").use_pallas
    assert not default_decision((64, 64, 64), backend="cpu").use_pallas
    assert default_decision((64, 64, 64), backend="tpu").bz == DEFAULT_BZ


def test_default_table_keeps_f64_on_xla_on_tpu():
    """The TPU's kernel compiler lowers no float64: pallas=None routes an
    f64 solve to XLA whatever its size."""
    assert not default_decision((128, 128, 128), backend="tpu",
                                dtype=jnp.float64).use_pallas
    assert default_decision((128, 128, 128), backend="tpu",
                            dtype=jnp.float32).use_pallas


def test_resolve_without_cache_is_the_default_table(cache):
    dec = resolve("7pt", (64, 64, 64), jnp.float64)
    assert dec.source == "default"
    assert dec == default_decision((64, 64, 64))


# -----------------------------------------------------------------------------
# cache round-trip
# -----------------------------------------------------------------------------

def test_cache_round_trip(cache):
    """A persisted entry wins over the default table, with identical
    choices after a write -> resolve cycle."""
    key = tune_key("7pt", (16, 16, 16), jnp.float32)
    save_cache({key: {"use_pallas": True, "bz": 16, "br": 64}})
    dec = resolve("7pt", (16, 16, 16), jnp.float32)
    assert dec == TuneDecision(use_pallas=True, bz=16, br=64, source="cache")
    # resolve again: memoized read, same decision
    assert resolve("7pt", (16, 16, 16), jnp.float32) == dec
    # the file itself round-trips the entry verbatim
    assert json.loads(cache.read_text())[key]["bz"] == 16


def test_cache_key_pins_all_four_coordinates(cache):
    key = tune_key("7pt", (16, 16, 16), jnp.float32)
    save_cache({key: {"use_pallas": True, "bz": 4, "br": None}})
    hit = resolve("7pt", (16, 16, 16), jnp.float32)
    assert (hit.source, hit.br) == ("cache", None)
    # a different stencil / grid / dtype misses back to the default table
    assert resolve("27pt", (16, 16, 16), jnp.float32).source == "default"
    assert resolve("7pt", (16, 16, 32), jnp.float32).source == "default"
    assert resolve("7pt", (16, 16, 16), jnp.float64).source == "default"


def test_corrupt_cache_degrades_to_default(cache):
    cache.write_text("{not json")
    dec = resolve("7pt", (16, 16, 16), jnp.float32)
    assert dec.source == "default"


def test_tune_is_idempotent_and_retune_remeasures(cache):
    """``tune`` sweeps once, then serves the cache; ``--retune`` forces a
    re-measure.  4³ keeps the sweep sub-second."""
    d1 = autotune.tune((4, 4, 4), "7pt", jnp.float32, repeats=1)
    assert d1.source == "cache"
    mtime = cache.stat().st_mtime_ns
    d2 = autotune.tune((4, 4, 4), "7pt", jnp.float32, repeats=1)
    assert d2 == d1
    assert cache.stat().st_mtime_ns == mtime        # no re-sweep
    autotune.tune((4, 4, 4), "7pt", jnp.float32, repeats=1, retune=True)
    assert cache.stat().st_mtime_ns >= mtime        # rewritten


# -----------------------------------------------------------------------------
# the consumers: options.pallas=None and PallasOp tile resolution
# -----------------------------------------------------------------------------

def test_session_resolves_pallas_auto_from_cache(cache):
    from repro.api import SolverOptions, SolverSession
    from repro.core.problems import make_problem

    prob = make_problem((8, 8, 8), "7pt")
    key = tune_key("7pt", (8, 8, 8), prob.b().dtype)
    # off-TPU the default table would say False; the cache says True
    save_cache({key: {"use_pallas": True, "bz": 8, "br": None}})
    # the problem's dtype follows the process-global x64 flag (suite-order
    # dependent); the options must agree with it
    opts = SolverOptions(maxiter=5, pallas=None,
                         f64=prob.b().dtype == jnp.float64)
    sess = SolverSession(prob, method="cg", options=opts)
    assert sess.options.pallas is True
    # and without the entry, auto resolves via the default table
    save_cache({})
    sess = SolverSession(prob, method="cg", options=opts)
    assert sess.options.pallas is False


def test_pallas_op_reads_tuned_tiles(cache):
    import numpy as np

    from repro.core.solvers import LocalOp
    from repro.kernels.pallas_op import PallasOp

    key = tune_key("7pt", (8, 8, 8), jnp.float32)
    save_cache({key: {"use_pallas": True, "bz": 4, "br": 64}})
    op = PallasOp(LocalOp(__import__(
        "repro.core.operators", fromlist=["STENCILS"]).STENCILS["7pt"]))
    x = jnp.ones((8, 8, 8), jnp.float32)
    assert op._tiles(x) == (4, 64)
    # a pinned bz wins over the cache (fused_cg pins its own tiling)
    pinned = PallasOp(LocalOp(op.stencil), bz=8)
    assert pinned._tiles(x) == (8, None)
    # and the tuned tiling produces the same matvec as the untuned one
    y_tuned = op.matvec(x)
    y_pinned = pinned.matvec(x)
    np.testing.assert_allclose(np.asarray(y_tuned), np.asarray(y_pinned),
                               rtol=1e-6, atol=1e-6)


def test_cli_smoke_writes_both_configs(cache, capsys):
    autotune.main(["--grid", "4", "4", "4", "--repeats", "1"])
    table = json.loads(cache.read_text())
    assert tune_key("7pt", (4, 4, 4), jnp.float32) in table
    entry = table[tune_key("7pt", (4, 4, 4), jnp.float32)]
    assert set(entry) >= {"use_pallas", "bz", "br", "backend", "timings"}
    out = capsys.readouterr().out
    assert "use_pallas=" in out and str(cache) in out
