"""Autotuner cache tests — cold search, JSON persistence, warm skip.

The tuner (kernels/autotune.py) measures candidate four-step variants and
bucket block_q tilings once per (shape, mode, backend), records the winner
in an in-memory table, and persists it to a backend-keyed JSON file so the
NEXT process skips the search.  Dispatch (`ops._tuned_block_q`,
`fourstep_planar(variant=None)`) treats the table as a pure dict read.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, ops

pytestmark = pytest.mark.kernels


@pytest.fixture()
def fresh_cache(tmp_path, monkeypatch):
    """A private cache dir with empty in-memory tables; restores the
    session tables afterwards so other tests keep their entries."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path))
    saved_tables = dict(autotune._TABLES)
    saved_loaded = set(autotune._LOADED)
    autotune._TABLES.clear()
    autotune._LOADED.clear()
    yield tmp_path
    autotune._TABLES.clear()
    autotune._TABLES.update(saved_tables)
    autotune._LOADED.clear()
    autotune._LOADED.update(saved_loaded)


def test_key_is_order_insensitive():
    assert autotune.key_of("bucket", s=64, m=2, n=4) == \
        autotune.key_of("bucket", n=4, m=2, s=64)


def test_candidate_factor_plans_cover_radix_splits():
    plans = autotune.candidate_factor_plans(4096)
    assert [64, 64] in plans
    assert [16, 16, 16] in plans
    for p in plans:
        assert int(np.prod(p)) == 4096


def test_cold_search_persists_and_warm_skips(fresh_cache):
    """The round-trip: cold search -> JSON on disk -> a fresh in-memory
    state (a new process) reloads the table and skips the search."""
    before = autotune.searches_run()
    ent = autotune.ensure_fourstep(64, batch=2, mode="direct", reps=1)
    assert autotune.searches_run() == before + 1
    assert ent["variant"] in ("fused", "two_pass", "xla")

    path = autotune.cache_path()
    assert path.exists()
    data = json.loads(path.read_text())
    assert data["version"] == autotune.SCHEMA_VERSION
    assert any(k.startswith("fourstep|") for k in data["entries"])

    # same process, same key: pure lookup, no new search
    again = autotune.ensure_fourstep(64, batch=2, mode="direct", reps=1)
    assert again == ent
    assert autotune.searches_run() == before + 1

    # simulate a new process: drop memory, keep disk
    autotune.clear(memory_only=True)
    warm = autotune.ensure_fourstep(64, batch=2, mode="direct", reps=1)
    assert warm["variant"] == ent["variant"]
    assert autotune.searches_run() == before + 1


def test_bucket_search_records_block_q_and_dispatch_uses_it(fresh_cache):
    """tune_bucket times real masked-dispatcher calls and the recorded
    block_q flows back through ops._tuned_block_q on the next dispatch."""
    ent = autotune.tune_bucket("bucket", 64, 2, 4, q=4, mode="direct",
                               reps=1)
    assert ent["block_q"] in (1, 2, 4)
    got = ops._tuned_block_q("bucket", 4, 10**9, "direct", s=64, m=2, n=4)
    assert got == ent["block_q"]
    # a miss falls back to the VMEM heuristic (bounded by batch)
    miss = ops._tuned_block_q("bucket", 4, 2, "interpret", s=999, m=2, n=4)
    assert 1 <= miss <= 4


def test_corrupt_cache_file_tolerated(fresh_cache):
    path = autotune.cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text("{not json")
    autotune.clear(memory_only=True)
    assert autotune.lookup("fourstep", L=64, mode="direct") is None
    # and recording over it heals the file
    autotune.record("fourstep", {"variant": "fused", "ms": 1.0},
                    L=64, mode="direct")
    assert json.loads(path.read_text())["entries"]


def test_fourstep_dispatch_honors_recorded_variant(fresh_cache):
    """fourstep_planar(variant=None) consults the table: pin an 'xla'
    entry and the jaxpr shows the platform FFT, no pallas_call."""
    import jax

    autotune.record("fourstep", {"variant": "xla", "ms": 0.1},
                    L=64, mode="direct")
    x = jnp.zeros((2, 64), jnp.float32)
    jaxpr = str(jax.make_jaxpr(
        lambda a, b: ops.fourstep_planar(a, b, interpret=None))(x, x))
    assert "fft" in jaxpr
    assert "pallas_call" not in jaxpr

    autotune.record("fourstep", {"variant": "fused",
                                 "factors": [4, 4, 4], "ms": 0.1},
                    L=64, mode="compiled")
    jaxpr = str(jax.make_jaxpr(
        lambda a, b: ops.fourstep_planar(a, b, interpret=False))(x, x))
    assert "fourstep_fft_multistep" in jaxpr


def test_tuned_streaming_blocks_flow_into_bucket_launch(fresh_cache):
    """A recorded streaming tiling is what the dispatcher launches with."""
    s, m, n = 1 << 17, 2, 4
    autotune.record("bucket", {"block_q": 2, "block_a": 128, "block_b": 64,
                               "ms": 1.0},
                    s=s, m=m, n=n, mode="compiled")
    bq, ba, bb = ops._streaming_blocks("bucket", "compiled", s=s, m=m, n=n)
    assert (bq, ba, bb) == (2, 128, 64)


def test_service_warmup_runs_search_once(fresh_cache):
    """FFTService.warmup() populates the table; a second service (same
    cache) performs zero additional searches."""
    from repro.serving.fft_service import FFTService, FFTServiceConfig

    cfg = FFTServiceConfig(s=64, m=2, n_workers=4, max_batch=4,
                           autotune_reps=1)
    FFTService(cfg).warmup(kinds=("c2c",))
    after_first = autotune.searches_run()
    assert after_first > 0
    FFTService(cfg).warmup(kinds=("c2c",))
    assert autotune.searches_run() == after_first


def test_failing_candidate_is_reported_and_skipped(fresh_cache, monkeypatch):
    """A candidate that cannot lower or compile is warned about with its
    error and skipped; the search still records a measured winner."""
    real = autotune._fourstep_candidate_fn

    def two_pass_refused(variant, factors, interpret):
        if variant != "two_pass":
            return real(variant, factors, interpret)

        def fn(xr, xi):
            raise RuntimeError("refused by the compiler")

        return fn

    monkeypatch.setattr(autotune, "_fourstep_candidate_fn", two_pass_refused)
    with pytest.warns(RuntimeWarning,
                      match="two_pass.*refused by the compiler"):
        ent = autotune.tune_fourstep(64, batch=2, mode="direct", reps=1)
    assert ent["variant"] != "two_pass"
    assert np.isfinite(ent["ms"])


def test_search_without_a_working_candidate_raises(fresh_cache, monkeypatch):
    """Every candidate failing is an error, not a recorded NaN default:
    a device that cannot run the kernels shows up at warmup."""
    def refused(variant, factors, interpret):
        def fn(xr, xi):
            raise RuntimeError("refused")

        return fn

    monkeypatch.setattr(autotune, "_fourstep_candidate_fn", refused)
    with pytest.warns(RuntimeWarning), \
            pytest.raises(autotune.AutotuneError,
                          match="every candidate failed"):
        autotune.tune_fourstep(64, batch=2, mode="compiled", reps=1)
    assert autotune.lookup("fourstep", L=64, mode="compiled") is None

    def bucket_refused(*args, **kwargs):
        raise RuntimeError("block refused")

    monkeypatch.setattr(ops, "coded_bucket_masked", bucket_refused)
    with pytest.warns(RuntimeWarning, match="block_q=.*block refused"), \
            pytest.raises(autotune.AutotuneError):
        autotune.tune_bucket("bucket", 64, 2, 4, q=2, mode="compiled",
                             reps=1)
    assert autotune.lookup("bucket", s=64, m=2, n=4, mode="compiled") is None
