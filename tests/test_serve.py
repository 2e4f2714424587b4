"""Factor-form serving engine (repro/serve).

Covers the three serving contracts: scoring correctness against the dense
materialized oracle, hot-swap semantics (zero recompiles inside a rank
bucket, in-flight batches complete against the model they were dispatched
with, no stale scores after a swap), and the no-implicit-transfer
discipline — dispatch and swap run under ``transfer_guard`` with the
engine's own compilation counter as the regression pin, mirroring
tests/test_engine.py's stats pins. Plus the checkpoint restore path
(``read_iterate_packed`` / ``from_checkpoint``) and the micro-batcher.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import serve
from repro.checkpoint import CheckpointStore, RunCheckpointer, read_iterate_packed
from repro.core import low_rank
from repro.core.frank_wolfe import EpochCarry

D, M = 40, 28


def _iterate(k, d=D, m=M, max_rank=12, seed=0, alpha=0.8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return low_rank.FactoredIterate(
        u=jnp.zeros((max_rank, d)).at[:k].set(jax.random.normal(ks[0], (k, d))),
        s=jnp.zeros((max_rank,)).at[:k].set(jax.random.normal(ks[1], (k,))),
        v=jnp.zeros((max_rank, m)).at[:k].set(jax.random.normal(ks[2], (k, m))),
        alpha=jnp.asarray(alpha, jnp.float32),
        count=jnp.asarray(k, jnp.int32),
    )


def _engine(max_batch=8, rank_block=8, **kw):
    return serve.ServingEngine(
        D, M, serve.ServeConfig(max_batch=max_batch, rank_block=rank_block, **kw)
    )


def _dense(it):
    return np.asarray(low_rank.materialize(it))


def _save_step(ckpt, t, it, d=D, m=M):
    carry = EpochCarry(
        state={"r": np.zeros(3, np.float32)}, iterate=it,
        comm_state=np.zeros(1, np.float32), t=np.asarray(t, np.int32),
        key=jax.random.PRNGKey(0),
    )
    ckpt.save_segment(
        t=t, carry=carry, history={k: [] for k in ("loss", "gap", "sigma", "gamma", "k")},
        masks=None, done=False,
    )
    ckpt.wait()


def _checkpointer(tmpdir, d=D, m=M):
    return RunCheckpointer(
        tmpdir, keep_last=None,
        extra=dict(task="MultiTaskLeastSquares", d=d, m=m, num_workers=1, comm="dense"),
    )


# ---------------------------------------------------------------------------
# Scoring correctness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch", [1, 3, 8])
@pytest.mark.parametrize("live", [0, 1, 5])
def test_score_matches_dense_oracle(batch, live):
    eng = _engine()
    it = _iterate(live)
    eng.load(it)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (batch, D)))
    np.testing.assert_allclose(eng.score(x), x @ _dense(it), rtol=1e-4, atol=1e-5)


def test_single_request_vector_and_transpose():
    it = _iterate(4)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(8), (M,)))
    eng = serve.ServingEngine(D, M, serve.ServeConfig(max_batch=4, transpose=True))
    eng.load(it)
    got = eng.score(x)
    assert got.shape == (1, D)
    np.testing.assert_allclose(got[0], _dense(it) @ x, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Hot-swap: the acceptance pins
# ---------------------------------------------------------------------------


def test_hot_swap_zero_recompiles_no_drops_no_stale_scores():
    """Swap mid-stream inside one rank bucket: the in-flight batch completes
    against the OLD model, post-swap traffic scores the NEW one, and the
    engine compiles exactly once — all without a single implicit
    device->host transfer (scores leave the device only via the handle's
    explicit ``block``). The compile/transfer bounds are the serving layer's
    own declaration (``ServingEngine.contract``), shared with
    ``tools/repro_contracts.py``; ``check_contract`` additionally walks every
    compiled executable's HLO for forbidden d x m materializations."""
    eng = _engine(rank_block=8, verify_kernels=False)
    contract = eng.contract(max_compilations=1)
    it_old, it_new = _iterate(3, seed=1), _iterate(7, seed=2)
    # Host-side packed models: the checkpoint-restore shape of a swap.
    packed_old = low_rank.pack_live(it_old)
    packed_new = low_rank.pack_live(it_new)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (5, D)))

    with contract.guard():
        eng.load(packed_old)
        in_flight = eng.score_async(x)
        model = eng.load(packed_new)  # swap while the batch is in flight
        after = eng.score_async(x)
        old_scores = in_flight.block()  # explicit transfer — allowed
        new_scores = after.block()

    eng.check_contract(contract)  # == 1 AOT build; no d x m in any executable
    assert eng.stats["compilations"] == 1, eng.stats  # same bucket, tight
    assert eng.stats["loads"] == 2 and eng.stats["dispatches"] == 2
    np.testing.assert_allclose(old_scores, x @ _dense(it_old), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(new_scores, x @ _dense(it_new), rtol=1e-4, atol=1e-5)
    # version stamps prove which model served each batch — no stale reads
    assert in_flight.version == 0 and after.version == model.version == 1


def test_bucket_crossing_compiles_once_per_bucket():
    eng = _engine(rank_block=4, verify_kernels=False)
    for live, want_compiles in ((0, 1), (2, 1), (4, 1), (5, 2), (8, 2), (3, 2)):
        eng.load(_iterate(live, seed=live))
        assert eng.stats["compilations"] == want_compiles, (live, eng.stats)
    # buckets stay cached: revisiting either costs nothing
    assert eng.stats["loads"] == 6


def test_rank_bucket_contract():
    assert serve.rank_bucket(0, 8) == 8  # untrained model shares bucket 1
    assert serve.rank_bucket(1, 8) == 8
    assert serve.rank_bucket(8, 8) == 8
    assert serve.rank_bucket(9, 8) == 16
    assert serve.rank_bucket(5, 1) == 5


def test_scorer_never_materializes_dxm():
    """Factor-form serving's core claim, checked on the compiled artifact:
    no executable — across rank buckets, plain and transposed — emits a
    (D, M) or (M, D) tensor. O(t(d+m)) per request, never O(dm)."""
    for transpose in (False, True):
        eng = _engine(rank_block=4, verify_kernels=False, transpose=transpose)
        eng.load(_iterate(3, seed=1))
        eng.load(_iterate(7, seed=2))  # second bucket -> second executable
        eng.check_contract()
        assert len(eng._compiled) == 2  # the walk covered both buckets


# ---------------------------------------------------------------------------
# Checkpoint restore path
# ---------------------------------------------------------------------------


def test_from_checkpoint_scores_and_follows_steps():
    it5, it9 = _iterate(5, seed=3), _iterate(9, seed=4)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(10), (4, D)))
    with tempfile.TemporaryDirectory() as td:
        ckpt = _checkpointer(td)
        _save_step(ckpt, 5, it5)
        eng = serve.ServingEngine.from_checkpoint(
            td, serve.ServeConfig(max_batch=4, rank_block=12, verify_kernels=False)
        )
        assert (eng.d, eng.m) == (D, M)  # sized from the manifest
        assert eng.model.step == 5 and eng.model.live_rank == 5
        np.testing.assert_allclose(eng.score(x), x @ _dense(it5), rtol=1e-4, atol=1e-5)

        # training writes a newer step; load(dir) follows latest, step= pins
        _save_step(ckpt, 9, it9)
        model = eng.load(td)
        assert model.step == 9 and eng.stats["compilations"] == 1  # same bucket
        np.testing.assert_allclose(eng.score(x), x @ _dense(it9), rtol=1e-4, atol=1e-5)
        model = eng.load(td, step=5)
        assert model.step == 5 and model.version == 2


def test_read_iterate_packed_roundtrips_pack_live():
    it = _iterate(6, seed=5)
    with tempfile.TemporaryDirectory() as td:
        _save_step(_checkpointer(td), 6, it)
        step, packed, extra = read_iterate_packed(td)
        assert step == 6 and extra["d"] == D
        want = low_rank.pack_live(it)
        for k in want:
            np.testing.assert_array_equal(packed[k], want[k])
        # and it re-pads to any capacity bit-exactly
        np.testing.assert_array_equal(
            np.asarray(low_rank.unpack_live(packed, 20).u[:6]), want["u"]
        )


def test_read_iterate_packed_rejects_foreign_checkpoints():
    with tempfile.TemporaryDirectory() as td:
        store = CheckpointStore(td)
        store.save_async(
            1, {"weights": np.ones(3, np.float32)}, extra={"payload_format": 1}
        )
        store.wait()
        with pytest.raises(ValueError, match="no packed iterate"):
            read_iterate_packed(td)
        store.save_async(2, {"x": np.ones(2, np.float32)}, extra={})
        store.wait()
        with pytest.raises(ValueError, match="payload format"):
            read_iterate_packed(td)


def test_engine_rejects_mismatched_checkpoint_dims():
    with tempfile.TemporaryDirectory() as td:
        _save_step(_checkpointer(td), 3, _iterate(3))
        eng = serve.ServingEngine(D + 1, M, serve.ServeConfig(verify_kernels=False))
        with pytest.raises(ValueError, match="serves"):
            eng.load(td)


# ---------------------------------------------------------------------------
# Micro-batcher
# ---------------------------------------------------------------------------


def test_microbatcher_accumulates_and_auto_flushes():
    eng = _engine(verify_kernels=False)
    it = _iterate(4, seed=6)
    eng.load(it)
    w = _dense(it)
    b = serve.MicroBatcher(eng, flush_at=4)
    qs = np.asarray(jax.random.normal(jax.random.PRNGKey(11), (6, D)))
    tickets = [b.submit(q) for q in qs]
    # 6 submits at flush_at=4: one auto-flush, two requests still queued
    assert eng.stats["dispatches"] == 1 and b.pending_count == 2
    assert tickets[3].dispatched and not tickets[4].dispatched
    # result() on a queued ticket flushes the tail rather than deadlocking
    np.testing.assert_allclose(tickets[5].result(), qs[5] @ w, rtol=1e-4, atol=1e-5)
    assert eng.stats["dispatches"] == 2 and b.pending_count == 0
    for i, t in enumerate(tickets):
        np.testing.assert_allclose(t.result(), qs[i] @ w, rtol=1e-4, atol=1e-5)
    assert eng.stats["dispatches"] == 2  # results are cached, not re-scored


def test_microbatcher_stamps_versions_across_swap():
    eng = _engine(verify_kernels=False)
    it0, it1 = _iterate(2, seed=7), _iterate(6, seed=8)
    eng.load(it0)
    b = serve.MicroBatcher(eng, flush_at=8)
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(12), (D,)))
    before = b.submit(q)
    b.flush()  # dispatched against v0
    queued = b.submit(q)  # still queued at swap time
    eng.load(it1)
    b.flush()  # dispatches against v1 — versions bind at dispatch, not submit
    assert before.version == 0 and queued.version == 1
    np.testing.assert_allclose(before.result(), q @ _dense(it0), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(queued.result(), q @ _dense(it1), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Guardrails
# ---------------------------------------------------------------------------


def test_engine_input_validation():
    eng = _engine(max_batch=4, verify_kernels=False)
    with pytest.raises(RuntimeError, match="no model"):
        eng.score(np.zeros((1, D), np.float32))
    eng.load(_iterate(2))
    with pytest.raises(ValueError, match="max_batch"):
        eng.score(np.zeros((5, D), np.float32))
    with pytest.raises(ValueError, match="scores"):
        eng.score(np.zeros((2, D + 1), np.float32))
    with pytest.raises(ValueError, match="missing"):
        eng.load({"u": np.zeros((1, D))})
    with pytest.raises(TypeError, match="cannot load"):
        eng.load(42)
    with pytest.raises(ValueError, match="max_batch"):
        serve.ServeConfig(max_batch=0)
    b = serve.MicroBatcher(eng)
    with pytest.raises(ValueError, match="one"):
        b.submit(np.zeros((2, D), np.float32))
    with pytest.raises(ValueError, match="flush_at"):
        serve.MicroBatcher(eng, flush_at=9)


def test_verify_factor_kernels_runs_on_first_load_only():
    eng = _engine()  # verify_kernels=True (default)
    eng.load(_iterate(2, seed=9))
    eng.load(_iterate(3, seed=10))  # second load must not re-verify (cheap swap)
    assert eng._verified
    err = serve.verify_factor_kernels(jax.random.PRNGKey(0), d=D, m=M)
    assert err < 1e-4


def test_verify_factor_kernels_catches_bf16_operands(monkeypatch):
    """A scoring kernel that rounds its request batch to bf16 trips the
    check: the dense oracle runs at Precision.HIGHEST."""
    from repro.serve import engine as serve_engine

    honest = serve_engine.fm_ops.factor_matvec

    def bf16_requests(x, *args, **kw):
        return honest(x.astype(jnp.bfloat16).astype(x.dtype), *args, **kw)

    monkeypatch.setattr(serve_engine.fm_ops, "factor_matvec", bf16_requests)
    with pytest.raises(AssertionError, match="diverge"):
        serve.verify_factor_kernels(jax.random.PRNGKey(0), d=D, m=M)
