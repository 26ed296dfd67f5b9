"""What runs inside the replica that holds the chip: the deployment class
the serve cells deploy through ``serve.run``.

``BenchLLM`` is ``LLMDeployment`` and ``BenchEngine`` is ``LLMEngine``: the
system under test, unchanged, with the benchmark's own stamps around the
calls into each layer (``chip_smoke.make_smoke_llm`` is the pattern). The
wrappers record; they decide nothing. Importing this module imports no jax.
"""

from __future__ import annotations

import threading
import time
import zlib
from typing import Any, Dict, List

import numpy as np

from ray_tpu.serve.llm import LLMDeployment, LLMEngine

now = time.monotonic   # CLOCK_MONOTONIC: one clock for driver and replica


def request_key(prompt) -> int:
    """How the driver and the replica name one request: its tokens."""
    a = np.asarray(prompt, np.int32).reshape(-1)
    return zlib.crc32(a.tobytes()) * 8192 + len(a) % 8192


class Recorder:
    """Spans and step facts, kept in memory until the run collects them."""

    def __init__(self):
        self.spans: List[tuple] = []        # (name, t0, t1)
        self.steps: List[tuple] = []        # (t0, t1, [(pos, fed, samples)])
        self.engine_ttft: Dict[int, float] = {}

    def span(self, name: str, t0: float, t1: float) -> None:
        self.spans.append((name, t0, t1))


class BenchEngine(LLMEngine):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.rec = Recorder()
        self.capture = False
        self.kv_round = None      # the calibration's fp8-KV control only
        self.last_logits = None
        self._dispatch = (0.0, 0.0)
        self._facts = []
        inner = self._step_fn

        def stamped(*a):
            t0 = now()
            out = inner(*a)
            self._dispatch = (t0, now())
            if self.kv_round is not None:
                out = (out[0], self.kv_round(out[1]))
            return out

        self._step_fn = stamped

    def submit(self, prompt, max_new_tokens, emit, **kw):
        key, t_sub, first = request_key(prompt), now(), [True]

        def stamped(item):
            if first[0] and isinstance(item, int):
                first[0] = False
                self.rec.engine_ttft[key] = now() - t_sub
            emit(item)

        return super().submit(prompt, max_new_tokens, stamped, **kw)

    def _sweep_and_admit(self):
        t0 = now()
        out = super()._sweep_and_admit()
        self.rec.span("admit", t0, now())
        return out

    def _advance_paged(self, jax, jnp):
        rows = []   # per active request: (cached, fed now, samples?)
        for req in self._slots:
            if req is None:
                continue
            left = len(req.prompt) - req.consumed
            n = min(self.prefill_chunk, left) if left > 0 else 1
            rows.append((req.pos, n, int(n >= left)))
        self._facts = rows
        t0 = now()
        out = super()._advance_paged(jax, jnp)
        t1 = now()
        d0, d1 = self._dispatch
        self.rec.span("build_inputs", t0, d0)
        self.rec.span("dispatch", d0, d1)
        self.rec.span("fetch_logits", d1, t1)
        return out

    def _sample(self, logits):
        if self.capture:
            self.last_logits = logits.copy()
        return super()._sample(logits)

    def step(self):
        t0, n0 = now(), self.stats["steps"]
        busy = super().step()
        t1 = now()
        if self.stats["steps"] > n0:
            self.rec.span("sample_emit", self.rec.spans[-1][2], t1)
            self.rec.steps.append((t0, t1, self._facts))
        return busy


class BenchLLM(LLMDeployment):
    """One replica of a serve cell: seeded weights in the served type, the
    engine with the configuration file's settings, and the probes."""

    def __init__(self, cell: Dict[str, Any], seed: int, overrides=None):
        import jax

        from benchmark import weights
        from ray_tpu.util.tpu_info import ensure_compile_cache

        # before this process's first compile: the weights program is its
        # longest (15-40 s), and the engine turns the cache on only when it
        # is built, after the weights
        ensure_compile_cache()
        t0 = now()
        self.cell, self.seed = cell, seed
        self.config_file = cell["config_file"]
        self.tconfig = weights.transformer_config(
            self.config_file, **(overrides or {}))
        self.params = weights.make_params(self.tconfig, seed)
        jax.block_until_ready(self.params)
        t1 = now()
        eng = self.config_file["engine"]
        super().__init__(
            self.tconfig, params=self.params, seed=seed,
            paged=eng["paged"], max_slots=eng["max_slots"],
            max_len=eng["max_len"], block_size=eng["block_size"],
            num_blocks=eng["num_blocks"], prefill_chunk=eng["prefill_chunk"],
            stream_batch=eng["stream_batch"])
        t2 = now()
        self._warm_up()
        self.setup = {"weights_s": t1 - t0, "engine_s": t2 - t1,
                      "warmup_s": now() - t2}
        self.marks: Dict[str, Any] = {}
        self._trace_dir = None

    def _engine_factory(self, *args, **kw):
        return BenchEngine(*args, **kw)

    # -- set-up ------------------------------------------------------------

    def _serve_local(self, samples, timeout_s: float = 600.0):
        """Serve (prompt, max_new) pairs through the engine from inside the
        replica; returns per sample the (token, logits-or-None) pairs."""
        eng, rows, left = self.engine, [], [len(samples)]
        done, errors = threading.Event(), []

        def sink(out):
            def emit(item):
                if isinstance(item, int):
                    out.append((item, eng.last_logits))
                    return
                if item is not None:
                    errors.append(item)
                left[0] -= 1
                if left[0] == 0:
                    done.set()
            return emit

        for prompt, max_new in samples:
            rows.append([])
            eng.submit(prompt, max_new, sink(rows[-1]))
        self._wake.set()
        if not done.wait(timeout_s):
            raise TimeoutError("the engine did not finish the local requests")
        if errors:
            raise RuntimeError(f"local request failed: {errors[0]!r}")
        return rows

    def _warm_up(self) -> None:
        """The step program has one shape (slots x chunk), so one request
        that prefills over two steps and decodes over two compiles (or
        finds in the cache) all the window will run."""
        n = self.engine.prefill_chunk + 3
        self._serve_local([(list(range(1, n + 1)), 3)])

    # -- probes ------------------------------------------------------------

    def bench_facts(self) -> Dict[str, Any]:
        import jax

        from benchmark import device

        return {
            **device.facts(self.setup),
            "param_dtypes": sorted({str(x.dtype) for x in
                                    jax.tree.leaves(self.engine.params)}),
            "param_bytes": sum(x.nbytes for x in
                               jax.tree.leaves(self.engine.params)),
            "kv_pool_bytes": sum(x.nbytes for x in
                                 jax.tree.leaves(self.engine._cache)),
            "matmul_precision": jax.config.jax_default_matmul_precision,
        }

    def bench_mark(self, name: str) -> Dict[str, Any]:
        """Counters at an instant: compilations per program from the
        ``device_plane`` registry, and the engine's own stats."""
        from benchmark import device

        mark = {"t": now(), "compiles": device.compile_counts(),
                "stats": dict(self.engine.stats),
                "n_steps": len(self.engine.rec.steps)}
        self.marks[name] = mark
        return mark

    def bench_cancel_inflight(self) -> int:
        """End of the window: fail what is still queued or in a slot, so
        that the run need not wait for the longest answer."""
        eng = self.engine
        with eng._lock:
            victims = [r for r in eng._slots if r is not None]
            victims += eng._pending
            eng._pending.clear()
            for r in victims:
                r.cancelled = True
        for r in victims:
            r.emit(RuntimeError("benchmark window over"))
        self._wake.set()
        t_end = now() + 30.0
        while any(r is not None for r in eng._slots) and now() < t_end:
            time.sleep(0.005)
        return len(victims)

    def bench_trace_start(self, trace_dir: str) -> float:
        from benchmark import trace_reduce

        self._trace_dir = trace_dir
        self._anchor = trace_reduce.start_trace(trace_dir)
        self._trace_t0 = now()
        return self._trace_t0

    def bench_trace_stop(self) -> Dict[str, Any]:
        import jax

        from benchmark import trace_reduce

        t1 = now()
        jax.profiler.stop_trace()
        events = trace_reduce.load_xplane(
            trace_reduce.find_xplane(self._trace_dir))
        spans = [s for s in self.engine.rec.spans
                 if s[2] >= self._trace_t0 and s[1] <= t1]
        return trace_reduce.reduce(
            events, host_spans=spans, anchor_ns=self._anchor,
            window=(self._trace_t0, t1),
            program=self.cell["step_program"])

    def bench_collect(self) -> Dict[str, Any]:
        rec = self.engine.rec
        return {"steps": rec.steps, "spans": rec.spans,
                "engine_ttft": rec.engine_ttft, "marks": self.marks,
                "kv_state": self.engine.kv_state(),
                "max_slots": self.engine.max_slots,
                "prefill_chunk": self.engine.prefill_chunk}

    def serve_captured(self, samples: List[tuple]):
        """``_serve_local`` with the logits of every sampled position kept."""
        self.engine.capture = True
        try:
            return self._serve_local(samples)
        finally:
            self.engine.capture = False

    def bench_check(self, samples: List[tuple],
                    control: bool = False) -> Dict[str, Any]:
        """Engine logits against the plain reference on this run's own
        weights, for a sample of this run's own requests (check.py);
        ``control`` puts the int8 reference in the engine's place."""
        from benchmark import check

        return check.logits_against_reference(
            self.params, samples, self.serve_captured(samples),
            self.config_file, int(self.cell["check"]["ref_len"]),
            control=control)
