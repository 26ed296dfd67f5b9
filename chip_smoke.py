"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py               # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4     # four chips: the sharded-train phase only
    python chip_smoke.py --rehearse-cpu [--chips 4]   # control-flow rehearsal

Both accelerator paths run through the public entry points, in actors that
reserve ``TPU`` — this process is the ray_tpu driver and never imports jax:

* train: ``ray_tpu.init`` -> ``JaxTrainer(ScalingConfig(use_tpu=True))`` whose
  loop builds a ``TrainLoopHelper`` for a Llama preset at full width and depth
  (sequence 2048, Pallas flash attention) and takes a few optimizer steps;
* serve: ``serve.run`` of an ``LLMDeployment`` (llama-1b, bf16 weights, paged
  KV) answering concurrent streaming requests through the handle, checked
  against ``models.generate`` in the same replica process.

Any failed check in any phase exits non-zero. The last line of stdout is the
device JSON — printed only when every phase passed on a TPU.
``--rehearse-cpu`` runs the same control flow at toy sizes on CPU devices
(interpret-mode kernels, no TPU reservation); it can never print that line
and always exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

TRAIN_MODEL = "llama-250m"   # llama-1b's scanned step needs 16.3 GiB at batch 1
TRAIN_BATCH, TRAIN_SEQ = 8, 2048
TRAIN_CALLS, STEPS_PER_CALL = 3, 2
# The serve replica computes in float32 at matmul precision "highest" (bf16
# weights all the same). At the MXU's default precision the paged engine and
# models.generate — two programs, two roundings — part at a near-tie after
# 12 to 34 greedy tokens on four of five prompts on a v5e, in bf16 and in
# float32 alike, while the
# engine agrees with itself across chunk sizes and prefix reuse and both
# agree 64/64 at "highest" (my chip runs, PR 21). Token equality is only a
# test of the serving path where rounding cannot decide it.
SERVE_MODEL, SERVE_DTYPE = "llama-1b", "float32"
SERVE_ENV = {"JAX_DEFAULT_MATMUL_PRECISION": "highest"}
SERVE = dict(max_slots=8, max_len=2048, block_size=16, num_blocks=1024,
             prefill_chunk=64)
PREFIX_TOKENS, SUFFIX_TOKENS, NEW_TOKENS, CONCURRENT = 256, 64, 64, 4
LOSS_PARITY_ATOL = 0.05      # bf16 steps under different reduction orders


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"smoke check failed: {what}")
    log(f"ok: {what}")


# ---------------------------------------------------------------------------
# Code below this line runs INSIDE the actors that hold the chip.
# ---------------------------------------------------------------------------

def _device_facts() -> dict:
    import jax

    from ray_tpu.util import tpu_info

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "hbm": tpu_info.hbm_usage(),
            "compile_cache": tpu_info.compile_cache_counters(),
            "compile_cache_dir": tpu_info.ensure_compile_cache()}


def _make_helper(cfg: dict, mesh_config, devices):
    import jax
    import optax

    from ray_tpu import models
    from ray_tpu.parallel.mesh import make_mesh
    from ray_tpu.train import TrainLoopHelper

    config = models.get_config(cfg["model"]).replace(loss_chunk=512)
    helper = TrainLoopHelper.create(
        lambda: models.init_params(jax.random.PRNGKey(cfg["seed"]), config),
        models.param_axes(config),
        lambda p, b: models.loss_and_metrics(p, b, config),
        optax.adamw(3e-4),
        mesh=make_mesh(mesh_config, devices=devices))
    return config, helper


def _batch(cfg: dict, vocab: int) -> dict:
    import numpy as np

    toks = np.random.default_rng(cfg["seed"]).integers(
        0, vocab, size=(cfg["batch"], cfg["seq"] + 1), dtype=np.int32)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


def _kernel_in_step(helper, batch) -> bool:
    """Does the lowered train step hold the Mosaic kernel?"""
    import jax

    sharded = jax.tree.map(
        lambda x: jax.device_put(x, helper.batch_sharding()), batch)
    with jax.set_mesh(helper.mesh):
        text = helper.step_fn.lower(helper.state, sharded).as_text()
    return "tpu_custom_call" in text


def _timed_steps(helper, batch, calls: int, n: int):
    import jax

    losses, secs = [], []
    for _ in range(calls):
        t0 = time.perf_counter()
        loss = float(jax.device_get(helper.run_steps(batch, n)["loss"]))
        secs.append(round(time.perf_counter() - t0, 3))
        losses.append(loss)
    return losses, secs


def train_loop(cfg: dict) -> None:
    """One-chip train phase: a few optimizer steps at full width."""
    import jax

    from ray_tpu import train
    from ray_tpu.parallel import MeshConfig

    config, helper = _make_helper(cfg, MeshConfig(fsdp=-1), jax.devices())
    batch = _batch(cfg, config.vocab_size)
    kernel = _kernel_in_step(helper, batch)
    losses, secs = _timed_steps(helper, batch, cfg["calls"], cfg["steps"])
    train.report({
        "device": _device_facts(), "model": cfg["model"],
        "n_layers": config.n_layers, "d_model": config.d_model,
        "batch": cfg["batch"], "seq": cfg["seq"],
        "steps_per_call": cfg["steps"], "losses": losses,
        "call_seconds": secs, "kernel_in_step": kernel,
    })


def train_loop_sharded(cfg: dict) -> None:
    """Four-chip phase: the same model, seed and batch on three meshes over
    all devices and on a one-device mesh, all in this one process."""
    import gc

    import jax

    from ray_tpu import train
    from ray_tpu.parallel import MeshConfig

    devs = jax.devices()
    meshes = [("fsdp4", MeshConfig(fsdp=4), devs),
              ("fsdp2_tp2", MeshConfig(fsdp=2, tp=2), devs),
              ("tp2_sp2", MeshConfig(fsdp=1, tp=2, sp=2), devs),
              ("one_device", MeshConfig(fsdp=1), devs[:1])]
    runs = {}
    for name, mesh_config, mesh_devs in meshes:
        config, helper = _make_helper(cfg, mesh_config, mesh_devs)
        batch = _batch(cfg, config.vocab_size)
        kernel = _kernel_in_step(helper, batch)
        losses, secs = _timed_steps(helper, batch, cfg["calls"],
                                    cfg["steps"])
        leaves = jax.tree.leaves(helper.state)
        stats = {d.id: d.memory_stats() or {} for d in devs}
        held = {d.id: 0 for d in devs}          # state bytes per device
        for leaf in leaves:
            for shard in leaf.addressable_shards:
                held[shard.device.id] += shard.data.nbytes
        runs[name] = {
            "losses": losses, "call_seconds": secs, "kernel_in_step": kernel,
            "state_bytes_total": sum(x.nbytes for x in leaves),
            "state_bytes_by_device": held,
            "devices_in_shardings": sorted(
                {d.id for leaf in leaves for d in leaf.sharding.device_set}),
            "in_use_by_device": {
                i: s.get("bytes_in_use") for i, s in stats.items()},
            "peak_by_device": {
                i: s.get("peak_bytes_in_use") for i, s in stats.items()},
        }
        del helper, leaves
        gc.collect()
    train.report({"device": _device_facts(), "model": cfg["model"],
                  "batch": cfg["batch"], "seq": cfg["seq"],
                  "device_ids": [d.id for d in devs], "runs": runs})


def make_smoke_llm(dtype: str):
    """The deployment class: ``LLMDeployment`` plus the two probes the smoke
    reads from the replica process. Built lazily so importing this file
    pulls in nothing but the stdlib. ``dtype``: activation dtype."""
    from ray_tpu.serve.llm import LLMDeployment, LLMEngine

    class SmokeLLM(LLMDeployment):
        def _engine_factory(self, model, params, **kw):
            from ray_tpu import models

            # bf16 weights (the preset's master dtype is float32)
            return LLMEngine(
                models.get_config(model).replace(param_dtype="bfloat16",
                                                 dtype=dtype),
                params, **kw)

        def device_facts(self):
            import jax

            facts = _device_facts()
            facts["param_dtypes"] = sorted(
                {str(x.dtype) for x in jax.tree.leaves(self.engine.params)})
            facts["matmul_precision"] = jax.config.jax_default_matmul_precision
            facts["n_layers"] = self.engine.config.n_layers
            facts["d_model"] = self.engine.config.d_model
            return facts

        def reference_generate(self, prompt, max_new_tokens):
            """``models.generate`` on this replica's own weights."""
            import jax.numpy as jnp

            from ray_tpu import models

            out = models.generate(
                self.engine.params, jnp.asarray([prompt], jnp.int32),
                self.engine.config, max_new_tokens=max_new_tokens)
            return [int(t) for t in out[0, len(prompt):]]

    return SmokeLLM


# ---------------------------------------------------------------------------
# Driver side (this process): orchestration only, no jax.
# ---------------------------------------------------------------------------

def _require_tpu(device: dict, count: int, rehearse: bool) -> None:
    if rehearse:
        check(device["platform"] == "cpu", "rehearsal ran on the CPU")
        return
    check(device["platform"] == "tpu",
          f"worker reports platform tpu (got {device['platform']!r})")
    check(device["count"] == count,
          f"worker sees {count} chip(s) (got {device['count']})")


def _wait_gone(pid: int, deadline_s: float = 60.0) -> bool:
    t_end = time.monotonic() + deadline_s
    while time.monotonic() < t_end:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return True
        time.sleep(0.05)
    return False


def _fit(loop, cfg: dict, n_tpu: int, rehearse: bool) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    scaling = (ScalingConfig(num_workers=1) if rehearse else
               ScalingConfig(num_workers=1, use_tpu=True,
                             resources_per_worker={"TPU": float(n_tpu)}))
    result = JaxTrainer(
        loop, train_loop_config=cfg, scaling_config=scaling,
        run_config=RunConfig(
            name="chip_smoke",
            storage_path=os.path.join(HERE, "chiprun_out", "smoke")),
    ).fit()
    check(bool(result.metrics.get("device")),
          "the worker's report came back through session.report")
    pid = result.metrics["device"]["pid"]
    check(pid != os.getpid(), "the loop ran in a worker, not in the driver")
    check(_wait_gone(pid),
          f"train worker pid {pid} is gone (chip released)")
    return result.metrics


def train_phase(args) -> dict:
    cfg = {"seed": args.seed, "model": TRAIN_MODEL, "batch": TRAIN_BATCH,
           "seq": TRAIN_SEQ, "calls": TRAIN_CALLS, "steps": STEPS_PER_CALL}
    if args.rehearse_cpu:
        cfg.update(model="llama-debug", batch=4, seq=128)
    t0 = time.monotonic()
    m = _fit(train_loop, cfg, 1, args.rehearse_cpu)
    log(f"train: {m['model']} L{m['n_layers']} d{m['d_model']} batch "
        f"{m['batch']} x seq {m['seq']}, {m['steps_per_call']} steps/call: "
        f"losses {m['losses']} call seconds {m['call_seconds']} (first call "
        f"includes the compile); phase {time.monotonic() - t0:.1f}s; hbm "
        f"{m['device']['hbm']}; compile cache {m['device']['compile_cache']}"
        f" at {m['device']['compile_cache_dir']}")
    _require_tpu(m["device"], 1, args.rehearse_cpu)
    losses = m["losses"]
    check(all(x == x and abs(x) != float("inf") for x in losses),
          "train losses are finite")
    check(losses[-1] < losses[0],
          f"loss fell over the steps ({losses[0]:.4f} -> {losses[-1]:.4f})")
    if not args.rehearse_cpu:  # interpret mode lowers to no Mosaic call
        check(m["kernel_in_step"],
              "the lowered train step holds tpu_custom_call (Pallas kernel)")
    return m["device"]


def sharded_phase(args) -> dict:
    cfg = {"seed": args.seed, "model": TRAIN_MODEL, "batch": TRAIN_BATCH,
           "seq": TRAIN_SEQ, "calls": 2, "steps": STEPS_PER_CALL}
    if args.rehearse_cpu:
        cfg.update(model="llama-debug", batch=4, seq=128)
    m = _fit(train_loop_sharded, cfg, 4, args.rehearse_cpu)
    _require_tpu(m["device"], 4, args.rehearse_cpu)
    ref = m["runs"]["one_device"]
    n_dev = len(m["device_ids"])
    check(n_dev == 4, "four devices in the worker")
    for name, run in m["runs"].items():
        log(f"mesh {name}: losses {run['losses']} call seconds "
            f"{run['call_seconds']} kernel {run['kernel_in_step']} state "
            f"bytes/device {run['state_bytes_by_device']} in use "
            f"{run['in_use_by_device']} peak {run['peak_by_device']}")
        check(all(x == x for x in run["losses"])
              and run["losses"][-1] < run["losses"][0],
              f"{name}: losses finite and falling")
        if name == "one_device":
            continue
        gaps = [abs(a - b) for a, b in zip(run["losses"], ref["losses"])]
        check(max(gaps) <= LOSS_PARITY_ATOL,
              f"{name}: loss matches the one-device mesh "
              f"(max gap {max(gaps):.4f} <= {LOSS_PARITY_ATOL})")
        check(run["devices_in_shardings"] == sorted(m["device_ids"]),
              f"{name}: every device appears in the state's shardings")
        # parameters spread: fsdp and tp both shard the weights, sp
        # replicates them — no device may hold more than that share (+10%
        # for the replicated norms and scalars)
        ways = {"fsdp4": 4, "fsdp2_tp2": 4, "tp2_sp2": 2}[name]
        share = run["state_bytes_total"] / ways
        held = run["state_bytes_by_device"].values()
        check(max(held) <= 1.1 * share,
              f"{name}: no device holds more than 1/{ways} of the state "
              f"(max {max(held)} vs share {share:.0f})")
        if not args.rehearse_cpu:
            # nothing else of size sits on any device once the steps are
            # done: in-use bytes = that device's state shard + slack (the
            # batch, metrics; 256 MiB)
            for dev_id, used in run["in_use_by_device"].items():
                check(used <= run["state_bytes_by_device"][dev_id]
                      + (256 << 20),
                      f"{name}: device {dev_id} holds its state shard and "
                      f"little else ({used} bytes in use)")
            check(run["kernel_in_step"] == (name != "tp2_sp2"),
                  f"{name}: kernel in the lowered step: "
                  f"{run['kernel_in_step']} (the sp ring path is XLA-only)")
    return m["device"]


def serve_phase(args) -> dict:
    import random

    from ray_tpu import serve

    model, kw = SERVE_MODEL, dict(SERVE)
    actor_opts = {"max_concurrency": 16, "num_cpus": 0,
                  "runtime_env": {"env_vars": SERVE_ENV}}
    if args.rehearse_cpu:
        model = "llama-debug"
        kw.update(max_len=128, num_blocks=64, prefill_chunk=8, block_size=8)
    else:
        actor_opts["resources"] = {"TPU": 1.0}
    n_prefix, n_suffix, n_new = ((32, 8, 16) if args.rehearse_cpu else
                                 (PREFIX_TOKENS, SUFFIX_TOKENS, NEW_TOKENS))
    t0 = time.monotonic()
    app = serve.deployment(make_smoke_llm(SERVE_DTYPE), name="SmokeLLM",
                           ray_actor_options=actor_opts).bind(
        model, seed=args.seed, **kw)
    handle = serve.run(app, name="chip_smoke")
    facts = handle.options(method_name="device_facts").remote().result(
        timeout_s=900)
    log(f"serve: replica up in {time.monotonic() - t0:.1f}s: {model} "
        f"L{facts['n_layers']} d{facts['d_model']} weights "
        f"{facts['param_dtypes']} {kw}")
    _require_tpu(facts, 1, args.rehearse_cpu)
    check(facts["param_dtypes"] == ["bfloat16"], "weights are bf16")
    check(facts["matmul_precision"] == "highest",
          "the replica computes at matmul precision highest")

    vocab = 256 if args.rehearse_cpu else 32000
    rng = random.Random(args.seed)
    prefix = [rng.randrange(vocab) for _ in range(n_prefix)]
    prompts = [prefix + [rng.randrange(vocab) for _ in range(n_suffix)]
               for _ in range(1 + CONCURRENT)]

    def stream(prompt, out, i):
        t_start = time.monotonic()
        try:
            toks = []
            for tok in handle.options(stream=True).remote(prompt, n_new):
                if not toks:
                    out[i] = {"ttft": time.monotonic() - t_start}
                toks.append(int(tok))
            out[i]["tokens"] = toks
            out[i]["seconds"] = time.monotonic() - t_start
        except Exception as e:  # a thread: the caller reports and fails
            out[i] = {"error": repr(e)}

    # request 0 alone: compiles the step, and its finished prompt blocks
    # seed the prefix cache the concurrent requests then hit
    results: dict = {}
    stream(prompts[0], results, 0)
    check("error" not in results[0], f"first request: {results[0]}")
    log(f"serve: first request (compile included) {results[0]['seconds']:.1f}s"
        f", ttft {results[0]['ttft']:.1f}s")
    threads = [threading.Thread(target=stream, args=(p, results, i))
               for i, p in enumerate(prompts[1:], start=1)]
    t1 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    dt = time.monotonic() - t1
    for i in range(1, 1 + CONCURRENT):
        check(i in results and "error" not in results[i]
              and len(results[i].get("tokens", ())) == n_new,
              f"request {i} streamed {n_new} tokens")
    log(f"serve: {CONCURRENT} concurrent requests x {n_new} tokens in "
        f"{dt:.2f}s ({CONCURRENT * n_new / dt:.1f} tokens/s through the "
        f"handle, prompts of {n_prefix + n_suffix}); ttft "
        f"{[round(results[i]['ttft'], 3) for i in range(1, 1 + CONCURRENT)]}")

    ref = handle.options(method_name="reference_generate").remote(
        prompts[1], n_new).result(timeout_s=900)
    agree = next((j for j, (a, b) in enumerate(zip(results[1]["tokens"], ref))
                  if a != b), n_new)
    check(results[1]["tokens"] == ref,
          f"greedy tokens equal models.generate on the same replica "
          f"({agree}/{n_new} agree)")
    kv = handle.options(method_name="kv_state").remote().result(timeout_s=60)
    check(kv["prefix"]["hits"] >= CONCURRENT,
          f"prefix cache hits: {kv['prefix']}")
    check(kv["kv_free"] + kv["prefix"]["nodes"] == kv["kv_total"],
          "no KV block leaked (free + cached = total)")
    facts = handle.options(method_name="device_facts").remote().result(
        timeout_s=60)
    log(f"serve: hbm {facts['hbm']}; compile cache {facts['compile_cache']}")
    serve.shutdown()
    return facts


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the sharded-train phase on four chips")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy sizes on CPU devices; never prints ok")
    args = ap.parse_args()

    from ray_tpu import _native

    # built here from native/*.cc — never a binary that travelled with
    # the copy (native/build/ is ignored by git)
    check(_native.build(force=True), "native store built from native/*.cc")

    import ray_tpu

    worker_env = {}
    if args.rehearse_cpu:
        worker_env = {
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "RTPU_ATTN_IMPL": "pallas", "RTPU_ATTN_PALLAS_INTERPRET": "1"}
    t0 = time.monotonic()
    ray_tpu.init(runtime_env={"env_vars": worker_env})
    check(_native.native_status().get("loaded", False),
          f"native store loaded: {_native.native_status()}")
    try:
        if not args.rehearse_cpu:
            # fail now, not after the gang's start-up timeout
            check(ray_tpu.cluster_resources().get("TPU", 0) >= args.chips,
                  f"this host has {args.chips} TPU chip(s) to reserve: "
                  f"{ray_tpu.cluster_resources()}")
        if args.chips == 4:
            device = sharded_phase(args)
        else:
            train_phase(args)
            device = serve_phase(args)
    finally:
        ray_tpu.shutdown()
    check("jax" not in sys.modules, "the driver never imported jax")
    log(f"all phases passed in {time.monotonic() - t0:.1f}s")
    if args.rehearse_cpu:
        log("rehearsal only: no chip was driven, so no result line")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
