"""Flagship benchmark: LLM train-step throughput + MFU on the local device.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

The metric is model FLOPs utilization (MFU) of a Llama-family training step
(fwd+bwd+adamw, bf16 matmuls) — the BASELINE.json north-star contract
("Llama-3-8B >=45% MFU on v5e-256"); ``vs_baseline`` is MFU/0.45. With
``JAX_PLATFORMS=cpu`` the same harness runs a tiny config and reports
tokens/s under a CPU-named metric, never an MFU.

The parent process never touches jax (a process that has touched jax holds
the chip): it runs the jax-free core microbenches, then ONE child process
for the train-step measurement. That child runs on the device it names or
fails — there is no probe, no retry, no cached result and no CPU fallback;
a failed measurement exits non-zero with the error in the JSON line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from ray_tpu import config as _rtpu_config  # jax-free

_CHILD_TIMEOUT_S = float(_rtpu_config.get("bench_child_timeout"))


# ---------------------------------------------------------------------------
# Parent: orchestrates, never imports jax, always prints one JSON line.
# ---------------------------------------------------------------------------

def main() -> int:
    detail: dict = {}
    # Core-runtime microbench first: pure ray_tpu (no jax on the driver
    # path).
    detail["core_microbench"] = _core_microbench()
    # Native-driver A/B (r14): same-container off/on comparison of the
    # GIL-free control-pipe engine + parallel data plane — the only
    # numbers that mean anything on container-throttled boxes.
    detail["native_pipe"] = _native_pipe_ab()
    # Streaming-shuffle bench (r6): out-of-core sort throughput + peak
    # RSS, so exchange regressions (a stage starting to materialize)
    # show up in the BENCH trajectory.
    detail["data_shuffle"] = _data_shuffle_bench()
    # Serving-tier A/Bs (r14): dense vs paged+prefix-reuse on the
    # shared-prefix replay trace, and round-robin vs load-aware routing
    # under skewed load — same-container, CPU-pinned.
    detail["serve_llm"] = _serve_llm_bench()
    # Disaggregated prefill/decode A/B (r16): colocated vs split pools
    # with KV-block shipping on the mixed long-prefill + steady-decode
    # trace — same-container, CPU-pinned.
    detail["serve_disagg"] = _serve_disagg_bench()
    # Multi-model serving plane A/Bs (r17): N models multiplexed through
    # arena-paged registries vs the Zipf-hottest subset statically
    # dedicated on the same fleet weight budget, and speculative on/off
    # on the greedy decode path — same-container, CPU-pinned.
    detail["serve_multiplex"] = _serve_multiplex_bench()

    child = _run_train_child()
    if child.get("ok"):
        result = child["result"]
        result.setdefault("detail", {}).update(detail)
        print(json.dumps(result))
        return 0
    print(json.dumps({
        "metric": "llama_train_mfu", "value": None, "unit": "mfu",
        "vs_baseline": None,
        "error": f"train-step measurement failed: {child['error']}"[-2000:],
        "detail": detail,
    }))
    return 1


def _run_train_child(timeout: float = _CHILD_TIMEOUT_S) -> dict:
    """Run the train-step measurement in a subprocess; parse its JSON tail."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--train-step"],
            capture_output=True, text=True, timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        return {"ok": False,
                "error": f"train-step child timed out after {timeout}s"}
    if proc.returncode == 0:
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return {"ok": True, "result": json.loads(line)}
                except json.JSONDecodeError:
                    continue
    tail = (proc.stderr or proc.stdout or "")[-1500:]
    return {"ok": False, "error": f"rc={proc.returncode}: {tail}"}


# ---------------------------------------------------------------------------
# Child: jax lives here. Prints one JSON line on success, raises otherwise.
# ---------------------------------------------------------------------------

def train_step_child() -> None:
    from ray_tpu.util.tpu_info import ensure_compile_cache, is_tpu_backend

    ensure_compile_cache()
    import jax

    on_tpu = is_tpu_backend()
    rl_rate = _rl_learner_bench(jax)
    result = _measure(jax, on_tpu)
    result["detail"]["attention_impl"] = (
        "pallas flash kernel" if on_tpu else "cpu backend: blockwise XLA")
    result["detail"]["rl_learner_grad_steps_per_s"] = rl_rate
    result["detail"]["rl_forward_exploration"] = _rl_forward_bench(jax)
    result["detail"]["decode"] = _decode_bench(jax, on_tpu)
    # device-plane section: the compiled-program registry this child
    # populated (compile wall times, cost-analysis flops, HBM
    # watermarks). Signature histories are dropped (they bloat the
    # one-line JSON without adding to the table).
    from ray_tpu.util import device_plane as _dp

    snap = _dp.snapshot(census=False) or {}
    rows = []
    for r in snap.get("programs") or ():
        r.pop("sigs", None)
        rows.append(r)
    dp_detail = {"programs": rows}
    if snap.get("hbm"):
        dp_detail["hbm"] = snap["hbm"]
    result["detail"]["device_plane"] = dp_detail
    print(json.dumps(result))


def _decode_bench(jax, on_tpu: bool) -> dict:
    """Serving-path throughput: greedy decode tokens/s on the flagship
    model (batch 8, prefill 128, 128 new tokens; the CPU run uses the same
    tiny config as the CPU train path). generate()'s decode loop is one
    lax.scan program, so the timing is a single dispatch with a final
    data-dependent read."""
    import numpy as np

    from ray_tpu import models

    name = "llama-250m" if on_tpu else "llama-debug"
    config = models.get_config(name).replace(remat=False)
    params = models.init_params(jax.random.PRNGKey(0), config)
    prompt = jax.numpy.asarray(np.random.default_rng(0).integers(
        0, config.vocab_size, (8, 128), dtype=np.int32))
    new = 128

    def run():
        out = models.generate(params, prompt, config,
                              max_new_tokens=new)
        # data-dependent read spanning the whole scan
        return int(jax.device_get(out[:, -1].astype(
            jax.numpy.int32).sum()))

    t0 = time.perf_counter()
    run()  # compile + warm
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    return {"tokens_per_sec": round(8 * new / dt, 1),
            "model": name, "batch": 8, "new_tokens": new,
            "prefill": 128, "compile_warm_s": round(compile_s, 1)}


def _rl_learner_bench(jax) -> float:
    """PPO learner grad-steps/s on this device (north-star: learner
    throughput vs the reference's 8xA100 DDP learner)."""
    import numpy as np

    from ray_tpu.rllib.ppo import PPOLearner

    spec = {"observation_dim": 84, "action_dim": 6, "discrete": True,
            "hidden": (256, 256)}
    learner = PPOLearner(spec, {"num_devices": 1}, seed=0)
    rng = np.random.default_rng(0)
    n = 4096
    batch = {
        "obs": rng.standard_normal((n, 84)).astype(np.float32),
        "actions": rng.integers(0, 6, n),
        "action_logp": np.full(n, -1.79, np.float32),
        "vf_preds": rng.standard_normal(n).astype(np.float32),
        "advantages": rng.standard_normal(n).astype(np.float32),
        "value_targets": rng.standard_normal(n).astype(np.float32),
    }
    # warm with the SAME (epochs, minibatch) signature as the timed
    # call: update() scans the whole epoch×minibatch plan as one
    # program, so a different num_epochs is a different program
    epochs = 4
    learner.update(batch, minibatch_size=512, num_epochs=epochs)
    t0 = time.perf_counter()
    learner.update(batch, minibatch_size=512, num_epochs=epochs)
    dt = time.perf_counter() - t0
    steps = epochs * (n // 512)
    return round(steps / dt, 1)


def _rl_forward_bench(jax) -> dict:
    """RLModule forward_exploration: jit vs eager speedup — the analog
    of the reference's one checked-in ML-library number (torch.compile
    forward_exploration speedups, rllib/benchmarks/torch_compile:
    +33.9% CPU ... +156.7% A100). jax.jit is the jax-native compile."""
    if jax.default_backend() != "cpu":
        # the reference's primary comparator is its CPU number; off the
        # CPU the eager arm measures per-op dispatch, not compile benefit
        return {"skipped": "CPU-only micro-bench"}
    import numpy as np

    from ray_tpu.rllib.rl_module import RLModuleSpec

    spec = RLModuleSpec(observation_dim=84, action_dim=6,
                        discrete=True, hidden=(256, 256))
    module = spec.build()
    params = module.init(jax.random.PRNGKey(0))
    obs0 = jax.numpy.asarray(
        np.random.default_rng(0).standard_normal(
            (32, 84)).astype(np.float32))
    rng = jax.random.PRNGKey(1)

    jitted = jax.jit(module.forward_exploration)

    def timed(fn, n=50):
        jax.block_until_ready(fn(params, obs0, rng))  # warm
        obs = obs0
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(params, obs, rng)
            # chain: next input depends on this output, so the final
            # device_get spans all n calls
            obs = obs0 + 0.0 * out["vf_preds"][:, None]
        float(jax.device_get(out["vf_preds"].sum()))
        return (time.perf_counter() - t0) / n

    eager_s = timed(module.forward_exploration)
    jit_s = timed(jitted)
    return {"eager_ms": round(eager_s * 1e3, 3),
            "jit_ms": round(jit_s * 1e3, 3),
            "speedup_pct": round((eager_s / jit_s - 1) * 100, 1)}


def _measure(jax, on_tpu: bool) -> dict:
    import numpy as np
    import optax

    from ray_tpu import models
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import TrainLoopHelper
    from ray_tpu.util.tpu_info import peak_flops_per_chip

    if on_tpu:
        # full-layer remat + blockwise LM-head loss: the compiled step is
        # 2.3 GiB arguments + 3.1 GiB temporaries on a 16 GB v5e
        # (tests/test_tpu_compile.py describes the chip and checks the fit)
        config = models.llama_250m().replace(loss_chunk=512)
        batch_size, seq = 8, 2048
        iters = 10
    else:
        config = models.llama_debug()
        batch_size, seq = 4, 128
        iters = 5

    n_dev = jax.device_count()
    helper = TrainLoopHelper.create(
        lambda: models.init_params(jax.random.PRNGKey(0), config),
        models.param_axes(config),
        lambda p, b: models.loss_and_metrics(p, b, config),
        optax.adamw(1e-4),
        mesh_config=MeshConfig(dp=1, fsdp=-1, tp=1, sp=1),
    )

    rng = np.random.default_rng(0)
    toks = rng.integers(0, config.vocab_size, size=(batch_size, seq + 1),
                        dtype=np.int32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    # The inner loop is a single scanned n-step program
    # (TrainLoopHelper.run_steps) — the idiomatic TPU loop: one dispatch +
    # one device_get per n steps; the returned loss chains through every
    # step's params, so the get spans all n steps. One warmup call
    # compiles the scanned program AND warms the chip; the single-step
    # program is never timed, so never compile it.
    metrics = helper.run_steps(batch, iters)
    float(jax.device_get(metrics["loss"]))

    t0 = time.perf_counter()
    metrics = helper.run_steps(batch, iters)
    loss = float(jax.device_get(metrics["loss"]))
    dt = (time.perf_counter() - t0) / iters

    tokens_per_step = batch_size * seq
    tokens_per_sec = tokens_per_step / dt
    # fwd+bwd ~= 6N FLOPs/token + attention term 12*L*d*s (causal halves it)
    flops_token = config.flops_per_token() + (
        6 * config.n_layers * config.hdim * config.n_heads * seq)
    model_flops = flops_token * tokens_per_sec
    # an unknown device_kind raises here: no MFU against a guessed peak
    peak = peak_flops_per_chip() * n_dev if on_tpu else None
    mfu = model_flops / peak if on_tpu else None

    # self-reporting perf trajectory: the measured step lands in the
    # train-telemetry metrics (HBM gauges included on-chip) and its
    # snapshot rides the bench JSON
    from ray_tpu.train import telemetry

    telemetry.record_step(dt, tokens=tokens_per_step, mfu=mfu,
                          loss=loss, steps=iters,
                          program="train::run_steps")
    tele = telemetry.snapshot()

    # cost-model attribution (device plane): achieved FLOP/s from the
    # registered run_steps program's XLA cost analysis. Detail only —
    # the headline keeps the hand 6N formula for cross-round
    # comparability (cost-analysis flops count remat recompute, so this
    # reads hardware utilization, not model MFU).
    cost_model = None
    from ray_tpu.util import device_plane as _dp

    fps = _dp.program_flops_per_step("train::run_steps")
    if fps:
        achieved = fps / dt
        cost_model = {
            "flops_per_step": fps,
            "achieved_flops_per_s": achieved,
            "mfu_cost_model": (round(achieved / peak, 4)
                               if on_tpu else None),
        }

    dev = jax.devices()[0]
    return {
        "metric": "llama_train_mfu" if on_tpu else "llama_train_tokens_per_sec_cpu",
        "value": round(mfu, 4) if on_tpu else round(tokens_per_sec, 1),
        "unit": "mfu" if on_tpu else "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4) if on_tpu else None,
        "detail": {
            "model": "llama-250m" if on_tpu else "llama-debug",
            "batch_size": batch_size,
            "tokens_per_sec": round(tokens_per_sec, 1),
            "step_time_ms": round(dt * 1e3, 2),
            "devices": n_dev,
            "backend": dev.platform,
            "device_kind": dev.device_kind,
            "timing_mode": ("scanned n-step program, single dependent "
                            "device_get"),
            "loss": loss,
            "telemetry": tele,
            "cost_model": cost_model,
        },
    }


# ---------------------------------------------------------------------------
# Core-runtime microbenchmark (reference analog:
# release/microbenchmark/run_microbenchmark.py — tasks/s, actor calls/s,
# put GB/s) on a throwaway local cluster. jax-free.
# ---------------------------------------------------------------------------

def _data_shuffle_bench() -> dict:
    """Out-of-core sort through the streaming exchange, scaled for a
    2-vCPU box: 24 MB of (key, payload) rows sorted under an 8 MB spill
    threshold. Reports rows/s (best-of-3 per the CLAUDE.md noise rule —
    capability, not average-under-load) and the peak per-process RSS
    growth over the run (max across driver + workers): a materializing
    regression shows up as peak_rss_mb jumping toward the dataset size."""
    import threading

    import numpy as np

    out = {}
    n_blocks, rows_per = 12, 125_000  # 12 x 125k x 16 B = 24 MB
    overrides = {
        "RTPU_STORE_CAPACITY": str(4 << 20),
        "RTPU_SPILL_THRESHOLD": str(8 << 20),
        "RTPU_DATA_EXCHANGE_RUN_BYTES": str(2 << 20),
        "RTPU_STORE_PREFAULT_BYTES": "0",
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    started = False
    try:
        import ray_tpu
        from ray_tpu.core.runtime import _get_runtime
        from ray_tpu.data.dataset import Dataset

        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
        started = True

        def gen():
            rng = np.random.default_rng(0)
            for i in range(n_blocks):
                yield {"key": rng.integers(0, 1 << 40, size=rows_per),
                       "pay": np.full(rows_per, float(i))}

        def _vmrss_kb(pid):
            try:
                with open(f"/proc/{pid}/status") as f:
                    for line in f:
                        if line.startswith("VmRSS:"):
                            return int(line.split()[1])
            except OSError:
                pass
            return None

        stop = threading.Event()
        rss = {}  # pid -> [base, peak]
        spill_peak = [0]

        def sample():
            while not stop.wait(0.05):
                pids = [os.getpid()]
                try:
                    pids += [ws.proc.pid for ws in
                             list(_get_runtime().workers.values())]
                except Exception:
                    pass
                for pid in pids:
                    kb = _vmrss_kb(pid)
                    if kb is None:
                        continue
                    ent = rss.setdefault(pid, [kb, kb])
                    ent[1] = max(ent[1], kb)
                try:
                    spill_peak[0] = max(
                        spill_peak[0],
                        ray_tpu.object_store_memory()["spilled_bytes"])
                except Exception:
                    pass

        def trial():
            t0 = time.perf_counter()
            rows = 0
            last = None
            for ref in Dataset(gen).sort(
                    "key", num_blocks=8).iter_block_refs():
                block = ray_tpu.get(ref)
                keys = block.get("key")
                if keys is None or not len(keys):
                    continue
                assert np.all(keys[1:] >= keys[:-1])
                assert last is None or keys[0] >= last
                last = keys[-1]
                rows += len(keys)
                ray_tpu.free(ref)
            assert rows == n_blocks * rows_per
            return rows / (time.perf_counter() - t0)

        trial()  # warm: pool spawn + first-exchange fixed costs
        t = threading.Thread(target=sample, daemon=True)
        t.start()
        try:
            out["sort_rows_per_s"] = round(max(trial() for _ in range(3)))
        finally:
            stop.set()
            t.join(timeout=5)
        out["peak_rss_mb"] = round(max(
            (peak - base) for base, peak in rss.values()) / 1024, 1)
        out["dataset_mb"] = round(n_blocks * rows_per * 16 / 1e6, 1)
        out["peak_spilled_mb"] = round(spill_peak[0] / 1e6, 1)
    except Exception as e:  # the bench must never die on the data side
        out["error"] = str(e)
    finally:
        if started:
            try:
                ray_tpu.shutdown()
            except Exception:
                pass
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def _native_pipe_ab() -> dict:
    """Same-container off/on A/B of the native driver (r14 tentpole):
    tasks/s, single- and multi-client shapes with pipe messages/task and
    driver-CPU/task (the r13 431 µs baseline comparator), and put GB/s
    against a PRE-WARMED arena (CLAUDE.md: the cold-arena zero-fill is a
    one-time cost that would otherwise drown the copy-path signal).
    Each mode boots a fresh runtime; everything else is identical."""
    import resource as _resource

    import numpy as np

    import ray_tpu

    def _pipe_msg_total():
        from ray_tpu.util.metrics import registry_records as _rr

        total = 0.0
        for rec in _rr():
            if rec["name"] != "rtpu_pipe_messages_total":
                continue
            for _k, v in rec["samples"]:
                total += v if not isinstance(v, tuple) else v[2]
        return total

    def one_mode(on: bool) -> dict:
        out: dict = {}
        os.environ["RTPU_NATIVE_PIPE"] = "1" if on else "0"
        started = False
        try:
            ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
            started = True

            @ray_tpu.remote
            def noop():
                return None

            for _ in range(3):
                ray_tpu.get([noop.remote() for _ in range(60)])
            if on:
                from ray_tpu.core.runtime import _get_runtime

                # dialed-back workers only: a replenishment spawn
                # mid-boot legitimately has no engine yet
                live = [ws for ws in _get_runtime().workers.values()
                        if ws.status != "dead" and ws.conn is not None]
                out["engine_attached"] = bool(live) and all(
                    ws.npipe is not None for ws in live)

            n = 600

            def tasks_trial():
                t0 = time.perf_counter()
                ray_tpu.get([noop.remote() for _ in range(n)])
                return n / (time.perf_counter() - t0)

            out["tasks_per_s"] = round(
                max(tasks_trial() for _ in range(3)), 1)

            @ray_tpu.remote
            class BatchClient:
                def small_value_batch(self, k):
                    ray_tpu.get([noop.remote() for _ in range(k)])
                    return k

            clients = [BatchClient.remote() for _ in range(2)]
            ray_tpu.get([c.small_value_batch.remote(10) for c in clients])
            best = None
            for _ in range(3):
                ru0 = _resource.getrusage(_resource.RUSAGE_SELF)
                cpu0 = ru0.ru_utime + ru0.ru_stime
                m0 = _pipe_msg_total()
                t0 = time.perf_counter()
                ray_tpu.get(
                    [c.small_value_batch.remote(250) for c in clients])
                wall = time.perf_counter() - t0
                ru1 = _resource.getrusage(_resource.RUSAGE_SELF)
                rec = {
                    "rate_per_s": round(500.0 / wall, 1),
                    "driver_cpu_us_per_task": round(
                        (ru1.ru_utime + ru1.ru_stime - cpu0) / 500.0
                        * 1e6, 1),
                    "pipe_msgs_per_task": round(
                        (_pipe_msg_total() - m0) / 500.0, 2),
                }
                if best is None or rec["rate_per_s"] > best["rate_per_s"]:
                    best = rec
            out["multi_client"] = best

            # put bandwidth, warm arena first (one throwaway burst of the
            # same footprint pre-faults the extents the timed burst hits)
            arr = np.random.default_rng(0).standard_normal(1 << 20)
            for _ in range(16):
                ray_tpu.put(arr)
            rates = []
            for _ in range(3):
                t0 = time.perf_counter()
                refs = [ray_tpu.put(arr) for _ in range(16)]
                rates.append(
                    16 * arr.nbytes / (time.perf_counter() - t0) / 1e9)
                del refs
            out["put_gb_per_s_warm"] = round(max(rates), 2)

            @ray_tpu.remote
            def do_put(nbytes, times):
                data = np.zeros(nbytes // 8)
                for _ in range(times):
                    ray_tpu.put(data)
                return times * nbytes

            ray_tpu.get(do_put.remote(1 << 16, 1))

            def multi_put_trial(nbytes=8 << 20, times=4, m=2):
                t0 = time.perf_counter()
                ray_tpu.get([do_put.remote(nbytes, times)
                             for _ in range(m)])
                return m * times * nbytes / (time.perf_counter() - t0) / 1e9

            out["multi_client_put_gb_per_s"] = round(
                max(multi_put_trial() for _ in range(3)), 2)
            for c in clients:
                ray_tpu.kill(c)
        except Exception as e:  # the bench must never die on the A/B
            out["error"] = str(e)[:300]
        finally:
            if started:
                try:
                    ray_tpu.shutdown()
                except Exception:
                    pass
        return out

    saved = os.environ.get("RTPU_NATIVE_PIPE")
    try:
        result = {"off": one_mode(False), "on": one_mode(True)}
    finally:
        if saved is None:
            os.environ.pop("RTPU_NATIVE_PIPE", None)
        else:
            os.environ["RTPU_NATIVE_PIPE"] = saved
    try:
        on, off = result["on"], result["off"]
        result["summary"] = {
            "tasks_ratio_on_off": round(
                on["tasks_per_s"] / off["tasks_per_s"], 3),
            "multi_vs_single_client_on": round(
                on["multi_client"]["rate_per_s"] / on["tasks_per_s"], 3),
            "driver_cpu_delta_us": round(
                on["multi_client"]["driver_cpu_us_per_task"]
                - off["multi_client"]["driver_cpu_us_per_task"], 1),
        }
    except Exception:
        pass
    return result


def _serve_llm_bench() -> dict:
    """Serving-tier same-container A/Bs (ISSUE 12). Two comparisons:

    - ``paged_ab``: the shared-prefix replay trace through one
      in-process engine, dense vs paged+prefix-reuse — tokens/s, TTFT
      p99, prefix hit rate (best-of-3 per the CLAUDE.md noise rule).
      Runs in a CPU-pinned child so the bench driver never touches jax
      (or the chip) for a control-plane measurement.
    - ``routing_ab``: round-robin vs load-aware routing on a 2-replica
      sleepy deployment with one replica pre-loaded — wall time to
      drain a burst (the router's job is to keep the burst off the busy
      replica)."""
    import subprocess

    out: dict = {}
    env = dict(os.environ, JAX_PLATFORMS="cpu", RTPU_TRACING="0")
    here = os.path.dirname(os.path.abspath(__file__))

    def engine_trial(paged: bool):
        code = ("from experiments.serve_replay import run_engine_ab; "
                "import json; print(json.dumps(run_engine_ab('quick', "
                f"paged={paged})))")
        p = subprocess.run([sys.executable, "-c", code], text=True,
                           capture_output=True, timeout=300, env=env,
                           cwd=here)
        if p.returncode != 0:
            raise RuntimeError(p.stderr[-500:])
        return json.loads(p.stdout.strip().splitlines()[-1])

    try:
        for label, paged in (("paged", True), ("dense", False)):
            trials = [engine_trial(paged) for _ in range(3)]
            # best-of-3 PER METRIC (capability, not one lucky run):
            # max throughput, min tail latency — the CLAUDE.md noise rule
            best = {
                "tokens_per_s": max(t["tokens_per_s"] for t in trials),
                "ttft_p99_s": min(t["ttft_p99_s"] for t in trials),
                "tpot_p99_s": min(t["tpot_p99_s"] for t in trials),
            }
            if "prefix_hit_rate" in trials[0]:
                best["prefix_hit_rate"] = max(
                    t["prefix_hit_rate"] for t in trials)
            out.setdefault("paged_ab", {})[label] = best
        pab = out.get("paged_ab", {})
        if "paged" in pab and "dense" in pab:
            out["paged_ab"]["speedup"] = round(
                pab["paged"]["tokens_per_s"]
                / max(pab["dense"]["tokens_per_s"], 1e-9), 2)
    except Exception as e:
        out["paged_ab_error"] = str(e)[-300:]

    try:
        out["routing_ab"] = _serve_routing_ab()
    except Exception as e:
        out["routing_ab_error"] = str(e)[-300:]
    return out


def _serve_disagg_bench() -> dict:
    """Colocated-vs-disaggregated same-container A/B (ISSUE 13): the
    mixed long-prefill + steady-decode replay trace through DEPLOYED
    two-replica apps — colocated routes whole requests load-aware over
    two mixed replicas; disaggregated dedicates one replica to prefill
    and one to decode with KV blocks shipped over the DeviceChannel
    path between them. Deployed (separate replica processes), not
    in-process: two engines sharing one jax CPU device serialize their
    steps on the device queue, which hands prefill interference right
    back to decode and erases the architecture delta. Same hardware,
    same trace, best-of-3 per metric (the CLAUDE.md noise rule); each
    trial is a CPU-pinned child so the bench driver never touches jax.
    The contract: disagg shows lower TPOT p99 at >= comparable
    tokens/s (long prefills stop stealing decode step-time)."""
    import subprocess

    out: dict = {}
    env = dict(os.environ, JAX_PLATFORMS="cpu", RTPU_TRACING="0")
    here = os.path.dirname(os.path.abspath(__file__))

    def trial(disagg: bool):
        code = ("from experiments.serve_replay import run_serve_replay; "
                "import json; print(json.dumps(run_serve_replay("
                f"'quick', replicas=2, paged=True, disagg={disagg}, "
                "mixed=True, max_clients=8)))")
        p = subprocess.run([sys.executable, "-c", code], text=True,
                           capture_output=True, timeout=600, env=env,
                           cwd=here)
        if p.returncode != 0:
            raise RuntimeError(p.stderr[-500:])
        return json.loads(p.stdout.strip().splitlines()[-1])

    try:
        leaks = 0
        for label, disagg in (("disagg", True), ("colocated", False)):
            trials = [trial(disagg) for _ in range(3)]
            leaks += sum(t.get("kv_leaks", 0) for t in trials)
            # best-of-3 PER METRIC: max throughput, min tail latency
            out[label] = {
                "tokens_per_s": max(t["tokens_per_s"] for t in trials),
                "ttft_p99_s": min(t["ttft_p99_s"] for t in trials),
                "tpot_p50_s": min(t["tpot_p50_s"] for t in trials),
                "tpot_p99_s": min(t["tpot_p99_s"] for t in trials),
            }
        out["kv_leaks"] = leaks
        if "disagg" in out and "colocated" in out:
            out["tpot_p99_speedup"] = round(
                out["colocated"]["tpot_p99_s"]
                / max(out["disagg"]["tpot_p99_s"], 1e-9), 2)
            out["tokens_ratio"] = round(
                out["disagg"]["tokens_per_s"]
                / max(out["colocated"]["tokens_per_s"], 1e-9), 2)
    except Exception as e:
        out["error"] = str(e)[-300:]
    return out


def _serve_multiplex_bench() -> dict:
    """Multi-model serving-plane same-container A/Bs (ISSUE 16).

    Two comparisons, best-of-3 per metric (the CLAUDE.md noise rule):
    - consolidation: the same 8-model Zipf trace and the same fleet
      weight budget (2 replicas x 2 model-slots) spent two ways —
      EVERY model served through multiplexed registries that page
      weights on demand, vs the Zipf-hottest 4 statically dedicated
      (requests for unhosted models hard-shed). Open-loop arrivals, so
      a shed is lost tokens at unchanged wall time.
    - speculative: ngram-draft speculative decoding on vs off on the
      greedy gpt2-debug path (token-exact by construction; the parity
      tests hold the guarantee, this holds the speedup).
    Each trial is a CPU-pinned child so the bench driver never touches
    jax. Rounds interleave all four arms and the wall budget stops
    WHOLE rounds, so both sides of each A/B keep equal trial counts."""
    import subprocess

    out: dict = {}
    env = dict(os.environ, JAX_PLATFORMS="cpu", RTPU_TRACING="0")
    here = os.path.dirname(os.path.abspath(__file__))

    def trial(call: str) -> dict:
        code = ("from experiments.serve_replay import run_multiplex_ab, "
                "run_spec_ab; import json; "
                f"print(json.dumps({call}))")
        p = subprocess.run([sys.executable, "-c", code], text=True,
                           capture_output=True, timeout=600, env=env,
                           cwd=here)
        if p.returncode != 0:
            raise RuntimeError(p.stderr[-500:])
        return json.loads(p.stdout.strip().splitlines()[-1])

    arms = {
        "multiplex": "run_multiplex_ab('quick', dedicated=False)",
        "dedicated": "run_multiplex_ab('quick', dedicated=True)",
        "spec_on": "run_spec_ab('quick', spec=True)",
        "spec_off": "run_spec_ab('quick', spec=False)",
    }
    trials: dict = {k: [] for k in arms}
    budget_s = float(os.environ.get("RTPU_BENCH_MUX_BUDGET_S", "900"))
    t0 = time.monotonic()
    try:
        for _ in range(3):
            for label, call in arms.items():
                trials[label].append(trial(call))
            if time.monotonic() - t0 > budget_s * 2 / 3:
                break  # whole rounds only: arms stay comparable
        for label, ts in trials.items():
            best = max(ts, key=lambda t: t["tokens_per_s"])
            row = {"tokens_per_s": max(t["tokens_per_s"] for t in ts),
                   "ttft_p99_s": min(t["ttft_p99_s"] for t in ts),
                   "trials": len(ts)}
            # counters come from the best-throughput trial: they are a
            # property of one coherent run, not a cross-run extremum
            for k in ("shed", "swaps_in", "swaps_out", "engines",
                      "hosted_models", "spec_accept_rate"):
                if k in best:
                    row[k] = best[k]
            out[label] = row
        out["consolidation_tokens_ratio"] = round(
            out["multiplex"]["tokens_per_s"]
            / max(out["dedicated"]["tokens_per_s"], 1e-9), 2)
        # lazy paging proof: the multiplex arm must have churned, not
        # just held everything resident
        out["paging_proven"] = bool(
            out["multiplex"].get("swaps_out", 0) > 0)
        out["spec_speedup"] = round(
            out["spec_on"]["tokens_per_s"]
            / max(out["spec_off"]["tokens_per_s"], 1e-9), 2)
    except Exception as e:
        out["error"] = str(e)[-300:]
    return out


def _serve_routing_ab() -> dict:
    import ray_tpu
    from ray_tpu import serve

    res: dict = {}
    started = False
    saved = os.environ.get("RTPU_SERVE_ROUTING")
    try:
        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
        started = True

        @serve.deployment(num_replicas=2, max_ongoing_requests=16)
        def sleepy(dt=0.05):
            import time as _t

            _t.sleep(dt)
            return 1

        handle = serve.run(sleepy.bind(), name="bench_routing")
        for _ in range(6):  # warm both replicas + their workers
            handle.remote(0.001).result(timeout_s=60)

        def trial(mode: str) -> float:
            os.environ["RTPU_SERVE_ROUTING"] = mode
            # skew: a DEEP queue of short calls pinned onto replica 0 —
            # the depth signal p2c routes on (burst depth stays below
            # it, so the load-aware picker keeps the whole burst on
            # replica 1; round-robin parks half of it behind the queue)
            skew = [handle._replicas[0].handle_request.remote(
                "__call__", (0.2,), {}) for _ in range(12)]
            time.sleep(0.15)  # let queue depths surface in the runtime
            t0 = time.perf_counter()
            rs = [handle.remote(0.05) for _ in range(10)]
            for r in rs:
                r.result(timeout_s=60)
            wall = time.perf_counter() - t0
            ray_tpu.get(skew, timeout=60)
            return wall

        # alternate modes so background noise hits both equally
        walls = {"rr": [], "p2c": []}
        for _ in range(2):
            for mode in ("rr", "p2c"):
                walls[mode].append(trial(mode))
        for mode, ws in walls.items():
            res[mode] = {"burst_wall_best_s": round(min(ws), 3),
                         "burst_wall_all_s": [round(w, 3) for w in ws]}
        res["speedup"] = round(
            res["rr"]["burst_wall_best_s"]
            / max(res["p2c"]["burst_wall_best_s"], 1e-9), 2)
        serve.delete("sleepy")
    finally:
        if saved is None:
            os.environ.pop("RTPU_SERVE_ROUTING", None)
        else:
            os.environ["RTPU_SERVE_ROUTING"] = saved
        if started:
            try:
                serve.shutdown()
                ray_tpu.shutdown()
            except Exception:
                pass
    return res


_DP_AB_CODE = r"""
import json, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from ray_tpu.util import device_plane as dp

f = dp.registered_jit(lambda x: x + 1.0,
                      name="bench::overhead_probe", component="bench")
x = jnp.zeros((8,))
f(x)  # compile once, outside both windows

def trial(n=2000):
    t0 = time.perf_counter()
    for _ in range(n):
        f(x)
    return n / (time.perf_counter() - t0)

best = lambda k, fn: max(fn() for _ in range(k))
dp.disable_device_plane()
off = best(3, trial)
dp.enable_device_plane()
on = best(3, trial)
print(json.dumps({"jit_calls_per_s_off": round(off, 1),
                  "jit_calls_per_s_on": round(on, 1),
                  "on_off_ratio": round(on / off, 3) if off else None}))
"""


def _device_plane_overhead_ab() -> dict:
    """Registered-jit wrapper cost, armed vs disarmed, in a CPU-pinned
    child (best-of-3 each per the CLAUDE.md noise rule)."""
    import subprocess

    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        here = os.path.dirname(os.path.abspath(__file__))
        p = subprocess.run([sys.executable, "-c", _DP_AB_CODE],
                           text=True, capture_output=True, timeout=300,
                           env=env, cwd=here)
        if p.returncode != 0:
            return {"error": p.stderr[-300:]}
        return json.loads(p.stdout.strip().splitlines()[-1])
    except Exception as e:
        return {"error": str(e)}


def _core_microbench() -> dict:
    import numpy as np

    import ray_tpu

    out = {}
    started = False
    try:
        ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
        started = True

        @ray_tpu.remote
        def noop():
            return None

        # warm the pool to steady state: the first bursts grow the pool to
        # its 4-worker cap (zygote spawns land mid-burst otherwise) — the
        # reference microbenchmark also times warm workers only
        for _ in range(3):
            ray_tpu.get([noop.remote() for _ in range(60)])

        def best_of(k, fn, ndigits=1):
            # Throughput CAPABILITY on a noisy shared-CPU box: background
            # processes can steal a core mid-sample and halve a short
            # loop's rate; the max over k short trials reads through that
            # transient noise.
            return round(max(fn() for _ in range(k)), ndigits)

        n = 600

        def tasks_trial():
            t0 = time.perf_counter()
            ray_tpu.get([noop.remote() for _ in range(n)])
            return n / (time.perf_counter() - t0)

        out["tasks_per_s"] = best_of(3, tasks_trial)

        # tracing on/off A/B on the SAME warm process tree (ISSUE 7
        # bench guard): the off number re-measures right before the on
        # number so a disabled-path cost regression (span() must stay
        # one dict get) or an enabled-path span-cost blowup both surface
        # in the JSON line. enable_tracing reaches the live workers over
        # their control pipes — no respawn between the two sides.
        try:
            from ray_tpu.util import tracing as _tracing

            t_off = best_of(3, tasks_trial)
            try:
                _tracing.enable_tracing()
                t_on = best_of(3, tasks_trial)
            finally:
                # a failed on-trial must not leave tracing armed for the
                # rest of the microbench (it would corrupt every later
                # number this guard exists to protect)
                _tracing.disable_tracing()
            out["tracing_overhead"] = {
                "tasks_per_s_off": t_off,
                "tasks_per_s_on": t_on,
                "on_off_ratio": round(t_on / t_off, 3) if t_off else None,
            }
        except Exception as e:
            out["tracing_overhead"] = {"error": str(e)}

        # profiling on/off A/B on the SAME warm process tree (ISSUE 9
        # bench guard, same contract as tracing_overhead): the disarmed
        # number re-measures right before the armed one so a
        # disarmed-path regression (profiling_enabled() must stay one
        # dict get) or an armed-at-default-Hz sampler cost > the 10%
        # acceptance bound both surface in the JSON line.
        try:
            from ray_tpu.util import profiling as _profiling

            p_off = best_of(3, tasks_trial)
            try:
                _profiling.enable_profiling()
                p_on = best_of(3, tasks_trial)
            finally:
                _profiling.disable_profiling()
            out["profiling_overhead"] = {
                "tasks_per_s_off": p_off,
                "tasks_per_s_on": p_on,
                "on_off_ratio": round(p_on / p_off, 3) if p_off else None,
                "hz": _profiling._hz(),
            }
        except Exception as e:
            out["profiling_overhead"] = {"error": str(e)}

        # events on/off A/B on the SAME warm process tree (ISSUE 18
        # bench guard). The plane defaults ON, so unlike tracing/
        # profiling the interesting direction is inverted: measure
        # disarmed first, then re-arm (the shipped default) and measure
        # again — the on/off ratio bounds what worker_spawn/worker_death
        # recording costs on the task hot path. MUST end re-armed:
        # leaving events off would silently disarm the default-on plane
        # for the rest of the microbench.
        try:
            from ray_tpu.util import events as _events

            _events.disable_events()
            try:
                e_off = best_of(3, tasks_trial)
            finally:
                _events.enable_events()
            e_on = best_of(3, tasks_trial)
            out["events_overhead"] = {
                "tasks_per_s_off": e_off,
                "tasks_per_s_on": e_on,
                "on_off_ratio": round(e_on / e_off, 3) if e_off else None,
            }
        except Exception as e:
            out["events_overhead"] = {"error": str(e)}

        # device plane on/off A/B (ISSUE 19 bench guard): the hot path
        # is NOT tasks/s — it's the RegisteredFunction.__call__ wrapper
        # around an already-compiled jit (one enabled-check + one
        # cache-size probe + one counted call when armed), so the A/B
        # drives a tiny jitted fn where wrapper cost is the dominant
        # term. Runs in a CPU-pinned child: the bench driver never
        # touches jax (a process that has touched jax holds the chip). Same
        # child measures disarmed-then-armed for a same-tree ratio.
        out["device_plane_overhead"] = _device_plane_overhead_ab()

        @ray_tpu.remote
        class A:
            def f(self):
                return None

        a = A.remote()
        ray_tpu.get(a.f.remote())

        # reference 1_1_actor_calls_sync: one call at a time
        def sync_trial():
            t0 = time.perf_counter()
            for _ in range(150):
                ray_tpu.get(a.f.remote())
            return 150 / (time.perf_counter() - t0)

        out["actor_calls_sync_per_s"] = best_of(3, sync_trial)

        # reference 1_1_actor_calls_async: burst submit, then drain
        def async_trial():
            t0 = time.perf_counter()
            ray_tpu.get([a.f.remote() for _ in range(n)])
            return n / (time.perf_counter() - t0)

        out["actor_calls_per_s"] = best_of(3, async_trial)

        # reference placement_group_create/removal rate
        from ray_tpu.util.placement_group import (placement_group,
                                                  remove_placement_group)

        def pg_trial():
            t0 = time.perf_counter()
            for _ in range(50):
                pg = placement_group([{"CPU": 1}], strategy="PACK")
                remove_placement_group(pg)
            return 50 / (time.perf_counter() - t0)

        out["pg_create_remove_per_s"] = best_of(3, pg_trial)

        # -- multi-client + n:n benches (reference ray_perf.py:189,232,146:
        # "multi client" = WORKER-side clients submitting core-API calls
        # from inside actors/tasks, not extra driver processes) -----------

        @ray_tpu.remote
        class BatchClient:
            def small_value_batch(self, n):
                ray_tpu.get([noop.remote() for _ in range(n)])
                return n

        clients = [BatchClient.remote() for _ in range(2)]
        ray_tpu.get([c.small_value_batch.remote(10) for c in clients])  # warm

        def multi_task_trial(n=250):
            t0 = time.perf_counter()
            ray_tpu.get([c.small_value_batch.remote(n) for c in clients])
            return len(clients) * n / (time.perf_counter() - t0)

        out["multi_client_tasks_async_per_s"] = best_of(3, multi_task_trial)

        # multi-client control-plane cost detail (ISSUE 10 acceptance:
        # pipe messages/task <= 2.5 from 5.0 after coalescing): frames +
        # driver CPU around one multi-client run
        try:
            import resource as _resource

            from ray_tpu.util.metrics import registry_records as _rr

            def _pipe_msg_total():
                total = 0.0
                for rec in _rr():
                    if rec["name"] != "rtpu_pipe_messages_total":
                        continue
                    for _k, v in rec["samples"]:
                        total += v if not isinstance(v, tuple) else v[2]
                return total

            _ru0 = _resource.getrusage(_resource.RUSAGE_SELF)
            _cpu0 = _ru0.ru_utime + _ru0.ru_stime
            _m0 = _pipe_msg_total()
            _t0 = time.perf_counter()
            ray_tpu.get([c.small_value_batch.remote(250) for c in clients])
            _wall = time.perf_counter() - _t0
            _ru1 = _resource.getrusage(_resource.RUSAGE_SELF)
            _n = 500.0
            out["multi_client_detail"] = {
                "pipe_msgs_per_task": round(
                    (_pipe_msg_total() - _m0) / _n, 2),
                "driver_cpu_us_per_task": round(
                    (_ru1.ru_utime + _ru1.ru_stime - _cpu0) / _n * 1e6, 1),
                "rate_per_s": round(_n / _wall, 1),
            }
        except Exception as e:
            out["multi_client_detail"] = {"error": str(e)}

        # -- compiled execution plane (ISSUE 10): same-container A/B of a
        # 2-stage actor pipeline — compiled-DAG pipelined invocations vs
        # the equivalent per-call actor-call chain loop ------------------
        try:
            from ray_tpu.dag import InputNode

            @ray_tpu.remote
            class Stage:
                def __init__(self, k):
                    self.k = k

                def apply(self, x):
                    return x + self.k

            s1, s2 = Stage.remote(1), Stage.remote(100)
            ray_tpu.get([s1.apply.remote(0), s2.apply.remote(0)])  # warm

            def chain_trial(n=200):
                t0 = time.perf_counter()
                for i in range(n):
                    ray_tpu.get(s2.apply.remote(s1.apply.remote(i)))
                return n / (time.perf_counter() - t0)

            chain_rate = best_of(3, chain_trial)

            with InputNode() as inp:
                dag = s2.apply.bind(s1.apply.bind(inp))
            compiled = dag.experimental_compile(max_in_flight=8)
            assert compiled.execute(0).get(timeout=60) == 101  # warm

            def compiled_trial(n=2000):
                t0 = time.perf_counter()
                # execute() self-backpressures at max_in_flight, draining
                # completed results into their futures — full pipelining
                futs = [compiled.execute(i, timeout=120)
                        for i in range(n)]
                vals = [f.get(timeout=120) for f in futs]
                assert vals[-1] == n - 1 + 101
                return n / (time.perf_counter() - t0)

            compiled_rate = best_of(3, compiled_trial)
            compiled.teardown()
            ray_tpu.kill(s1)
            ray_tpu.kill(s2)
            out["compiled_dag"] = {
                "compiled_pipelined_per_s": compiled_rate,
                "actor_chain_per_s": chain_rate,
                "speedup": (round(compiled_rate / chain_rate, 1)
                            if chain_rate else None),
            }
        except Exception as e:
            out["compiled_dag"] = {"error": str(e)}

        @ray_tpu.remote
        def nn_work(actors, n):
            ray_tpu.get([actors[i % len(actors)].f.remote()
                         for i in range(n)])
            return n

        nn_actors = [A.options(num_cpus=0).remote() for _ in range(2)]
        ray_tpu.get([x.f.remote() for x in nn_actors])
        ray_tpu.get(nn_work.remote(nn_actors, 10))  # warm

        def nn_trial(m=2, n=150):
            t0 = time.perf_counter()
            ray_tpu.get([nn_work.remote(nn_actors, n) for _ in range(m)])
            return m * n / (time.perf_counter() - t0)

        out["n_n_actor_calls_async_per_s"] = best_of(3, nn_trial)

        @ray_tpu.remote
        def do_put(nbytes, times):
            data = np.zeros(nbytes // 8)
            for _ in range(times):
                ray_tpu.put(data)
            return times * nbytes

        ray_tpu.get(do_put.remote(1 << 16, 1))  # warm

        def multi_put_trial(nbytes=8 << 20, times=4, m=2):
            t0 = time.perf_counter()
            ray_tpu.get([do_put.remote(nbytes, times) for _ in range(m)])
            return m * times * nbytes / (time.perf_counter() - t0) / 1e9

        out["multi_client_put_gb_per_s"] = best_of(3, multi_put_trial,
                                                   ndigits=2)
        for x in nn_actors + clients:
            ray_tpu.kill(x)

        # numpy payload rides the zero-copy out-of-band buffer path (the
        # realistic ML case; raw bytes pickle in-band)
        arr = np.random.default_rng(0).standard_normal(1 << 20)  # 8 MiB
        nbytes = arr.nbytes

        # each trial pairs a fresh put burst with a COLD first read of its
        # own refs, so best-of never selects a warm re-read rate
        put_rates, get_rates = [], []
        for _ in range(3):
            t0 = time.perf_counter()
            refs = [ray_tpu.put(arr) for _ in range(16)]
            put_rates.append(16 * nbytes / (time.perf_counter() - t0) / 1e9)
            t0 = time.perf_counter()
            for r in refs:
                ray_tpu.get(r)
            get_rates.append(16 * nbytes / (time.perf_counter() - t0) / 1e9)
        out["put_gb_per_s"] = round(max(put_rates), 2)
        out["get_gb_per_s"] = round(max(get_rates), 2)

        # scalability-envelope analogs (reference
        # release/benchmarks/single_node.json: 10k get / wait / many
        # actors), scaled to this box so the numbers are comparable
        # across rounds
        refs1k = [ray_tpu.put(i) for i in range(1000)]
        t0 = time.perf_counter()
        ready, _ = ray_tpu.wait(refs1k, num_returns=1000, timeout=120)
        out["wait_1k_refs_s"] = round(time.perf_counter() - t0, 3)
        refs10k = [ray_tpu.put(i) for i in range(10000)]
        t0 = time.perf_counter()
        vals = ray_tpu.get(refs10k)
        out["get_10k_s"] = round(time.perf_counter() - t0, 3)
        assert vals[9999] == 9999
        t0 = time.perf_counter()
        actors = [A.options(num_cpus=0).remote() for _ in range(16)]
        ray_tpu.get([x.f.remote() for x in actors])
        out["actors_launched_per_s"] = round(
            16 / (time.perf_counter() - t0), 2)
        for x in actors:
            ray_tpu.kill(x)

        # spawn->ready latency behind actors_launched (ISSUE 4: the
        # zygote histogram attributes launch rate to worker-boot
        # queueing, not scheduler overhead) + the hottest locks of the
        # whole microbench — near-zero waits mean the driver is
        # CPU-bound, not lock-bound
        try:
            from ray_tpu.util import contention
            from ray_tpu.util.metrics import registry_records

            for rec in registry_records():
                if rec["name"] == "rtpu_worker_spawn_seconds":
                    for key, (counts, s, n) in rec["samples"]:
                        if n:
                            out.setdefault("spawn_latency", {})[
                                dict(key).get("mode", "?")] = {
                                "n": n, "mean_s": round(s / n, 3)}
            out["contention_hot"] = contention.top_waits(3)
        except Exception:
            pass
    except Exception as e:  # bench must never fail on the micro side
        out["error"] = str(e)
    finally:
        if started:
            try:
                ray_tpu.shutdown()
            except Exception:
                pass
    return out


if __name__ == "__main__":
    if "--train-step" in sys.argv:
        train_step_child()
    else:
        sys.exit(main())
