"""Train library: session/report, JaxTrainer fit, restart, checkpoints,
pjit train-step helper."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import ray_tpu
from ray_tpu import models
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.train import (
    Checkpoint, FailureConfig, JaxTrainer, RunConfig, ScalingConfig,
    TrainLoopHelper, load_pytree, save_pytree,
)
from ray_tpu.train.train_state import create_train_state, state_shardings


@pytest.fixture
def rt_train(tmp_path):
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield str(tmp_path)
    ray_tpu.shutdown()


def test_jax_trainer_reports_and_checkpoints(rt_train):
    storage = rt_train

    def loop(config):
        import ray_tpu.train as train

        for step in range(3):
            ckpt = None
            if step == 2:
                import tempfile, pickle

                d = tempfile.mkdtemp()
                with open(os.path.join(d, "model.pkl"), "wb") as f:
                    pickle.dump({"w": step * config["lr"]}, f)
                ckpt = Checkpoint(d)
            train.report({"step": step, "loss": 1.0 / (step + 1)},
                         checkpoint=ckpt)

    trainer = JaxTrainer(
        loop,
        train_loop_config={"lr": 0.5},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t1", storage_path=storage),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 2
    assert result.metrics["loss"] == pytest.approx(1 / 3)
    assert result.checkpoint is not None
    # rank dirs inside the checkpoint
    ranks = sorted(os.listdir(result.checkpoint.path))
    assert "rank_0" in ranks and "rank_1" in ranks


def test_jax_trainer_worker_error_raises(rt_train):
    def loop(config):
        raise RuntimeError("boom")

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=rt_train),
    )
    from ray_tpu.train import TrainingFailedError

    with pytest.raises(TrainingFailedError):
        trainer.fit()


def test_jax_trainer_restart_resumes_from_checkpoint(rt_train):
    marker = os.path.join(rt_train, "fail_once")

    def loop(config):
        import ray_tpu.train as train

        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            import pickle

            rank_dir = os.path.join(ckpt.path, "rank_0")
            with open(os.path.join(rank_dir, "state.pkl"), "rb") as f:
                start = pickle.load(f)["step"] + 1
        for step in range(start, 4):
            import pickle, tempfile

            d = tempfile.mkdtemp()
            with open(os.path.join(d, "state.pkl"), "wb") as f:
                pickle.dump({"step": step}, f)
            train.report({"step": step, "resumed_from": start},
                         checkpoint=Checkpoint(d))
            if step == 1 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("injected failure after step 1")

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(storage_path=rt_train,
                             failure_config=FailureConfig(max_failures=1)),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 3
    assert result.metrics["resumed_from"] == 2  # resumed after step-1 ckpt


def test_jax_trainer_dataset_ingest(rt_train):
    import ray_tpu.data as rdata

    ds = rdata.from_items([{"x": float(i)} for i in range(40)],
                          parallelism=4)

    def loop(config):
        import ray_tpu.train as train

        it = train.get_dataset_shard("train")
        total = 0.0
        count = 0
        for batch in it.iter_batches(batch_size=5):
            total += float(batch["x"].sum())
            count += len(batch["x"])
        train.report({"total": total, "count": count})

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(storage_path=rt_train),
        datasets={"train": ds},
    )
    result = trainer.fit()
    # rank 0 saw a proper split; both ranks together cover everything —
    # check via the count being half the rows (round-robin 4 blocks / 2)
    assert result.metrics["count"] == 20


def test_save_load_pytree_roundtrip(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.ones((4,), np.int32)}}
    save_pytree(tree, str(tmp_path))
    back = load_pytree(str(tmp_path))
    np.testing.assert_array_equal(back["a"], tree["a"])
    np.testing.assert_array_equal(back["b"]["c"], tree["b"]["c"])


def test_train_loop_helper_llama_loss_decreases():
    c = models.llama_debug()
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, c.vocab_size)
    batch = {"tokens": np.asarray(toks)}

    helper = TrainLoopHelper.create(
        lambda: models.init_params(jax.random.PRNGKey(0), c),
        models.param_axes(c),
        lambda p, b: models.loss_and_metrics(p, b, c),
        optax.adamw(3e-3),
        mesh_config=MeshConfig(dp=2, fsdp=2, tp=2),
    )
    losses = [float(helper.run_step(batch)["loss"]) for _ in range(6)]
    assert losses[-1] < losses[0]
    assert int(jax.device_get(helper.state["step"])) == 6


def test_state_shardings_cover_opt_state():
    c = models.llama_debug()
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    params = models.init_params(jax.random.PRNGKey(0), c)
    opt = optax.adam(1e-3)
    state = create_train_state(params, opt)
    sh = state_shardings(state, models.param_axes(c), mesh)
    # moments follow params; counts replicate
    flat_state = jax.tree.leaves(state)
    flat_sh = jax.tree.leaves(sh)
    assert len(flat_state) == len(flat_sh)


def test_session_checkpoint_seq_resumes_past_existing(tmp_path):
    """A fresh session in a trial dir with pre-crash checkpoints must number
    new ones AFTER them, or name-sorted "latest" resumes stale state
    (ADVICE r1)."""
    from ray_tpu.train.session import _Session, TrainContext

    (tmp_path / "checkpoint_000003").mkdir()
    (tmp_path / "checkpoint_000011").mkdir()
    ctx = TrainContext(trial_dir=str(tmp_path))
    s = _Session(lambda: None, ctx)
    assert s._checkpoint_seq == 12
    # empty dir starts at zero
    s2 = _Session(lambda: None, TrainContext(trial_dir=str(tmp_path / "new")))
    assert s2._checkpoint_seq == 0


def test_async_checkpoint_snapshot_semantics(tmp_path):
    """save_pytree_async snapshots device values at CALL time — mutating
    (donating) the arrays afterwards must not corrupt the write — and
    errors surface at wait()."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import load_pytree, save_pytree_async

    tree = {"w": jnp.arange(1000, dtype=jnp.float32)}
    h = save_pytree_async(tree, str(tmp_path / "ck"))
    # overwrite the source immediately (donation pattern)
    tree["w"] = tree["w"] * 0 - 1.0
    h.wait(timeout=60)
    assert h.done()
    back = load_pytree(str(tmp_path / "ck"))
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.arange(1000, dtype=np.float32))

    bad = save_pytree_async({"x": jnp.zeros(3)},
                            "/proc/definitely/not/writable")
    with pytest.raises(BaseException):
        bad.wait(timeout=60)


def test_profile_steps_captures_trace(tmp_path):
    """TrainLoopHelper.profile_steps writes an XLA trace and still returns
    step metrics."""
    import jax
    import optax

    from ray_tpu import models
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import TrainLoopHelper

    c = models.llama_debug()
    helper = TrainLoopHelper.create(
        lambda: models.init_params(jax.random.PRNGKey(0), c),
        models.param_axes(c),
        lambda p, b: models.loss_and_metrics(p, b, c),
        optax.sgd(1e-2),
        mesh_config=MeshConfig(dp=1, fsdp=-1, tp=1, sp=1),
    )
    toks = np.zeros((8, 17), np.int32)
    logdir = tmp_path / "trace"
    m = helper.profile_steps({"tokens": toks}, 2, str(logdir))
    assert np.isfinite(float(jax.device_get(m["loss"])))
    produced = list(logdir.rglob("*"))
    assert produced, "no trace files written"


def test_run_step_rejects_indivisible_batch_loudly():
    import jax
    import optax

    from ray_tpu import models
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import TrainLoopHelper

    c = models.llama_debug()
    helper = TrainLoopHelper.create(
        lambda: models.init_params(jax.random.PRNGKey(0), c),
        models.param_axes(c),
        lambda p, b: models.loss_and_metrics(p, b, c),
        optax.sgd(1e-2),
        mesh_config=MeshConfig(dp=1, fsdp=-1, tp=1, sp=1),
    )
    with pytest.raises(ValueError, match="does not divide"):
        helper.run_step({"tokens": np.zeros((3, 17), np.int32)})


def test_xla_compiler_options_knob(monkeypatch):
    """RTPU_XLA_COMPILER_OPTIONS parses to per-jit compiler options (the
    alternative to TPU flags in XLA_FLAGS, which abort XLA's host-side
    flag parser) and a jitted step
    still runs with a benign option set."""
    import jax
    import numpy as np
    import optax

    from ray_tpu.train.train_state import _compiler_options, make_train_step

    monkeypatch.setenv("RTPU_XLA_COMPILER_OPTIONS", "")
    assert _compiler_options() is None

    monkeypatch.setenv("RTPU_XLA_COMPILER_OPTIONS",
                       "xla_llvm_disable_expensive_passes=true a=1,b=2")
    assert _compiler_options() == {
        "xla_llvm_disable_expensive_passes": True, "a": 1, "b": 2}

    # quoted values opt out of coercion: string-typed options whose value
    # looks numeric/bool stay strings (ADVICE r5)
    monkeypatch.setenv("RTPU_XLA_COMPILER_OPTIONS",
                       "a='123' b=\"true\" c=123")
    assert _compiler_options() == {"a": "123", "b": "true", "c": 123}

    monkeypatch.setenv("RTPU_XLA_COMPILER_OPTIONS", "not-kv")
    with pytest.raises(ValueError):
        _compiler_options()

    # end-to-end: a CPU-valid option compiles and runs
    monkeypatch.setenv("RTPU_XLA_COMPILER_OPTIONS",
                       "xla_llvm_disable_expensive_passes=true")
    step = make_train_step(
        lambda p, b: ((p["w"] * b["x"]).sum() ** 2, {}),
        optax.sgd(0.1))
    state = {"step": jnp.zeros((), jnp.int32),
             "params": {"w": jnp.ones((4,))},
             "opt_state": optax.sgd(0.1).init({"w": jnp.ones((4,))})}
    out, _ = step(state, {"x": jnp.asarray(np.ones(4, np.float32))})
    assert int(out["step"]) == 1


# ---------------------------------------------------------------------------
# Elastic membership (r20): crash-atomic checkpoints, reform convergence,
# enriched death errors, end-to-end elastic resume
# ---------------------------------------------------------------------------

def test_save_pytree_crash_atomic_markers(tmp_path):
    """A completed save leaves no ``.tmp-`` litter and carries the
    ``.metadata.json`` completeness marker; a dir missing the marker or
    holding temp litter reads as torn (resume must skip it)."""
    from ray_tpu.train.trainer import _is_torn_save_dir

    d = tmp_path / "rank_0"
    save_pytree({"w": np.ones((3,), np.float32)}, str(d))
    entries = os.listdir(d)
    assert not any(e.startswith(".tmp-") for e in entries)
    assert ".metadata.json" in entries
    assert not _is_torn_save_dir(str(d))
    # user-set metadata survives the save's marker merge
    from ray_tpu.train.checkpoint import Checkpoint as Ckpt

    Ckpt(str(d)).update_metadata({"step": 7})
    save_pytree({"w": np.zeros((3,), np.float32)}, str(d))
    assert Ckpt(str(d)).get_metadata()["step"] == 7
    # kill-before-marker shape: payloads present, marker missing
    os.remove(d / ".metadata.json")
    assert _is_torn_save_dir(str(d))
    # kill-mid-rename shape: temp litter next to a marker
    save_pytree({"w": np.ones((3,), np.float32)}, str(d))
    (d / ".tmp-state_pytree.npz").write_bytes(b"partial")
    assert _is_torn_save_dir(str(d))
    # non-pytree checkpoints (user-managed files) carry no contract
    u = tmp_path / "user"
    u.mkdir()
    (u / "model.pkl").write_bytes(b"x")
    assert not _is_torn_save_dir(str(u))


def test_latest_checkpoint_world_size_stamp_and_torn_dirs(tmp_path):
    """Resume-point selection: all-ranks-ok judged against each
    checkpoint's own ``.world_size`` stamp (elastic runs change size
    between checkpoints), torn rank dirs and unreadable stamps skipped."""
    from ray_tpu.train.trainer import _latest_checkpoint

    def mk(name, ws=None, oks=(), ranks=(), torn_rank=None):
        d = tmp_path / name
        d.mkdir()
        if ws is not None:
            (d / ".world_size").write_text(str(ws))
        for r in oks:
            (d / f".rank_{r}.ok").write_text("")
        for r in ranks:
            (d / f"rank_{r}").mkdir()
        if torn_rank is not None:
            rd = d / f"rank_{torn_rank}"
            np.savez(rd / "state_pytree.npz")  # payload, no marker
        return str(d)

    assert _latest_checkpoint(str(tmp_path), 2) is None
    # complete at the stamped (shrunken) world size 1 — even though the
    # caller's requested size is 2
    c0 = mk("checkpoint_000000", ws=2, oks=(0, 1), ranks=(0, 1))
    c1 = mk("checkpoint_000001", ws=1, oks=(0,), ranks=(0,))
    assert _latest_checkpoint(str(tmp_path), 2) == c1
    # missing a rank marker for its stamp: skipped, falls back to c1
    mk("checkpoint_000002", ws=2, oks=(0,), ranks=(0, 1))
    assert _latest_checkpoint(str(tmp_path), 2) == c1
    # newest is complete -> wins
    c3 = mk("checkpoint_000003", ws=2, oks=(0, 1), ranks=(0, 1))
    assert _latest_checkpoint(str(tmp_path), 2) == c3
    # a torn rank dir (killed mid save_pytree) disqualifies the dir
    mk("checkpoint_000004", ws=1, oks=(0,), ranks=(0,), torn_rank=0)
    assert _latest_checkpoint(str(tmp_path), 2) == c3
    # unreadable stamp: do not trust the dir
    c5 = mk("checkpoint_000005", oks=(0, 1), ranks=(0, 1))
    (tmp_path / "checkpoint_000005" / ".world_size").write_text("junk")
    assert _latest_checkpoint(str(tmp_path), 2) == c3
    # pre-elastic dirs (no stamp) judged against the caller's size
    os.remove(tmp_path / "checkpoint_000005" / ".world_size")
    assert _latest_checkpoint(str(tmp_path), 2) == c5
    assert c0  # silence unused warning


def _stub_executor(monkeypatch, probes, fail_first_starts=0):
    """BackendExecutor with placement/spawn stubbed: ``probes`` feeds
    successive _placeable_world_size() answers; the first
    ``fail_first_starts`` start() calls die (double preemption: a node
    lost while the NEW group places)."""
    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.backend_executor import BackendExecutor

    ex = BackendExecutor(BackendConfig(),
                         ScalingConfig(num_workers=4, min_workers=1))
    ex._spec = {"train_fn": lambda: None, "loop_config": {},
                "trial_dir": "/tmp/x", "experiment_name": "x",
                "datasets": {}}
    calls = {"starts": [], "launches": [], "shutdowns": 0}
    it = iter(probes)
    monkeypatch.setattr(ex, "_placeable_world_size", lambda: next(it))
    monkeypatch.setattr(ex, "shutdown",
                        lambda: calls.__setitem__(
                            "shutdowns", calls["shutdowns"] + 1))

    def fake_start(num_workers=None):
        calls["starts"].append(num_workers)
        if len(calls["starts"]) <= fail_first_starts:
            raise ConnectionError("node lost during placement")
        ex._world_size = num_workers

    monkeypatch.setattr(ex, "start", fake_start)
    monkeypatch.setattr(ex, "_launch_sessions",
                        lambda ckpt: calls["launches"].append(ckpt))
    return ex, calls


def test_reform_double_preemption_converges(monkeypatch):
    """A second preemption DURING re-form fails that attempt; the next
    attempt re-probes (shrunken) capacity and lands — no livelock, and
    the world epoch reflects every fencing attempt."""
    ex, calls = _stub_executor(monkeypatch, probes=[3, 2],
                               fail_first_starts=1)
    assert ex.reform("/ckpt/5", reason="shrink") == 2
    assert calls["starts"] == [3, 2]          # re-probe, not retry-at-3
    assert calls["launches"] == ["/ckpt/5"]   # sessions resume from ckpt
    assert ex.world_epoch == 2                # one bump per fence
    assert ex.world_size == 2


def test_reform_floor_and_attempt_bound(monkeypatch):
    """Capacity below min_workers raises ElasticWorldSizeError (the
    group-restart fallback owns it); persistent churn exhausts the
    attempt bound instead of livelocking."""
    from ray_tpu.train.backend_executor import (
        ElasticWorldSizeError, TrainingWorkerError)

    ex, _ = _stub_executor(monkeypatch, probes=[0])
    with pytest.raises(ElasticWorldSizeError):
        ex.reform(None)
    ex2, calls2 = _stub_executor(monkeypatch, probes=[3, 3, 3],
                                 fail_first_starts=3)
    with pytest.raises(TrainingWorkerError) as ei:
        ex2.reform(None, attempts=3)
    assert not isinstance(ei.value, ElasticWorldSizeError)
    assert len(calls2["starts"]) == 3
    # reform before start_training is a caller bug, not a retry case
    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.backend_executor import BackendExecutor

    with pytest.raises(TrainingWorkerError):
        BackendExecutor(BackendConfig(),
                        ScalingConfig(num_workers=2)).reform(None)


def test_maybe_expand_only_when_capacity_returns(monkeypatch):
    ex, calls = _stub_executor(monkeypatch, probes=[2, 4])
    ex._world_size = 2
    assert ex.maybe_expand("/ckpt/1") is None      # probe says 2: no-op
    assert calls["starts"] == []
    assert ex.maybe_expand("/ckpt/2") == 4         # capacity returned
    assert calls["starts"] == [4]
    assert calls["launches"] == ["/ckpt/2"]
    ex._world_size = 4
    assert ex.maybe_expand("/ckpt/3") is None      # at requested size


class _FakeWorkers:
    """worker_group stand-in: each worker's next_result.remote() hands
    back a sentinel the monkeypatched ray_tpu.get resolves."""

    class _W:
        def __init__(self, outcome):
            class _M:
                def __init__(self, outcome):
                    self._o = outcome

                def remote(self, timeout):
                    return self._o

            self.next_result = _M(outcome)

    def __init__(self, outcomes):
        self.workers = [self._W(o) for o in outcomes]


def _fake_get(monkeypatch):
    def get(ref, **kw):
        if isinstance(ref, BaseException):
            raise ref
        return ref

    monkeypatch.setattr(ray_tpu, "get", get)


def test_get_next_results_names_dead_ranks_and_node_events(monkeypatch):
    """A dead rank surfaces as WorkerDeathError carrying WHICH ranks
    died and the node events recorded since the last drain — not a bare
    'inconsistent worker states'."""
    from ray_tpu.core.exceptions import ActorDiedError
    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.backend_executor import (
        BackendExecutor, WorkerDeathError)

    ex = BackendExecutor(BackendConfig(), ScalingConfig(num_workers=2))
    ex.worker_group = _FakeWorkers([
        ("result", {"step": 1}, None),
        ActorDiedError("actor's node died"),
    ])
    ex._node_events.append({"event": "down", "node_id": "deadbeef",
                            "cause": "heartbeat_timeout"})
    _fake_get(monkeypatch)
    with pytest.raises(WorkerDeathError) as ei:
        ex.get_next_results()
    e = ei.value
    assert sorted(e.dead_ranks) == [1]
    assert isinstance(e.dead_ranks[1], ActorDiedError)
    assert e.node_events and e.node_events[0]["event"] == "down"
    msg = str(e)
    assert "rank(s) [1]" in msg and "heartbeat_timeout" in msg
    # the drain is a drain: a second failure reports only fresh events
    assert ex.drain_node_events() == []


def test_get_next_results_lockstep_protocol_error(monkeypatch):
    """Some ranks done while others still report() is a training-loop
    bug (mismatched per-rank report counts) — raised as
    TrainingProtocolError, never retried as a death."""
    from ray_tpu.train.backend import BackendConfig
    from ray_tpu.train.backend_executor import (
        BackendExecutor, TrainingProtocolError, WorkerDeathError)

    ex = BackendExecutor(BackendConfig(), ScalingConfig(num_workers=2))
    ex.worker_group = _FakeWorkers([
        ("done", None, None),
        ("result", {"step": 3}, None),
    ])
    _fake_get(monkeypatch)
    with pytest.raises(TrainingProtocolError) as ei:
        ex.get_next_results()
    assert not isinstance(ei.value, WorkerDeathError)
    assert "rank(s) [0]" in str(ei.value)
    # a user exception propagates UNCHANGED (group-restart budget owns it)
    ex.worker_group = _FakeWorkers([ValueError("loop bug"),
                                    ("result", {}, None)])
    with pytest.raises(ValueError, match="loop bug"):
        ex.get_next_results()


def test_jax_trainer_elastic_rank_death_resumes_without_burning_budget(
        rt_train):
    """End-to-end elastic path on the local runtime: rank 0 SIGKILLs its
    own process mid-run. With min_workers set the trainer fences,
    re-forms, and resumes from the last all-ranks-ok checkpoint WITHOUT
    consuming a max_failures attempt (max_failures=0 here, so any
    group-restart would have failed the run), bumping world_epoch and
    emitting train_world_epoch."""
    marker = os.path.join(rt_train, "killed_once")

    def loop(config):
        import pickle, signal, tempfile

        import ray_tpu.train as train

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "rank_0", "state.pkl"),
                      "rb") as f:
                start = pickle.load(f)["step"] + 1
        for step in range(start, 4):
            d = tempfile.mkdtemp()
            with open(os.path.join(d, "state.pkl"), "wb") as f:
                pickle.dump({"step": step}, f)
            train.report({"step": step, "epoch": ctx.world_epoch,
                          "resumed": ctx.resumed_from or ""},
                         checkpoint=Checkpoint(d))
            if (step == 1 and ctx.world_rank == 0
                    and not os.path.exists(config["marker"])):
                open(config["marker"], "w").close()
                os.kill(os.getpid(), signal.SIGKILL)

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2, min_workers=1),
        run_config=RunConfig(storage_path=rt_train,
                             failure_config=FailureConfig(max_failures=0)),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 3
    assert result.metrics["epoch"] >= 1          # post-reform session
    assert result.metrics["resumed"]             # resumed from a ckpt
    from ray_tpu.util import state

    evs = [e for e in state.list_events(limit=10000)
           if e.get("name") == "train_world_epoch"]
    assert evs, "reform must emit train_world_epoch"
    assert evs[-1].get("reason") == "shrink"
    assert int(evs[-1].get("epoch", 0)) >= 1
