"""TrainState + pjit train-step factory: the TPU training inner loop.

Green-field relative to the reference (its inner loop is the user's torch
code; Ray only sees epoch-granularity reports, SURVEY §3.4). Here the
framework owns a canonical pjit training step because the sharding layout
(params on fsdp/tp, batch on dp×fsdp, sequence on sp) is framework policy:

- params/opt-state are placed by logical-axis rules (ZeRO-3 ≡ fsdp axis);
- the step is jitted once with donated state (buffers reused in HBM);
- gradients come out of ``jax.grad`` already averaged across the data axes
  by XLA (the loss is a global mean — no explicit allreduce anywhere).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import DEFAULT_RULES, ShardingRules

TrainState = Dict[str, Any]   # {"step", "params", "opt_state"}


def create_train_state(
    params: Any,
    optimizer: optax.GradientTransformation,
) -> TrainState:
    return {
        "step": jnp.zeros((), jnp.int32),
        "params": params,
        "opt_state": optimizer.init(params),
    }


def state_shardings(
    state: TrainState,
    param_axes: Any,
    mesh: Mesh,
    rules: Optional[ShardingRules] = None,
) -> TrainState:
    """NamedSharding pytree for a TrainState: opt-state moments inherit the
    param sharding they correspond to (ZeRO: optimizer state sharded like
    params); scalars replicate."""
    rules = rules or DEFAULT_RULES
    param_shardings = jax.tree.map(
        lambda axes: NamedSharding(mesh, rules.spec(axes)),
        param_axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )
    replicated = NamedSharding(mesh, P())

    params_struct = jax.tree.structure(state["params"])

    def opt_leaf_sharding(leaf):
        # optax states are pytrees whose array leaves either mirror the param
        # tree (moments) or are scalars (counts).
        if jax.tree.structure(leaf) == params_struct:
            return param_shardings
        return jax.tree.map(lambda _: replicated, leaf)

    opt_shardings = jax.tree.map(
        opt_leaf_sharding, state["opt_state"],
        is_leaf=lambda x: jax.tree.structure(x) == params_struct or not isinstance(x, (tuple, list, dict)),
    )
    return {
        "step": replicated,
        "params": param_shardings,
        "opt_state": opt_shardings,
    }


def make_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], Tuple[jax.Array, Dict]],
    optimizer: optax.GradientTransformation,
    *,
    donate: bool = True,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict]]:
    """Build a jittable ``(state, batch) -> (state, metrics)`` step.

    Call it under ``jax.set_mesh(mesh)`` with sharded state — XLA inserts
    all collectives (grad psum over dp/fsdp, all-gathers for fsdp params,
    ring permutes for sp attention).
    """

    def step(state: TrainState, batch: Dict[str, jax.Array]):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], batch)
        updates, opt_state = optimizer.update(
            grads, state["opt_state"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        new_state = {
            "step": state["step"] + 1,
            "params": params,
            "opt_state": opt_state,
        }
        return new_state, metrics

    from ray_tpu.util.device_plane import registered_jit

    return registered_jit(step, name="train::step", component="train",
                          donate_argnums=(0,) if donate else (),
                          compiler_options=_compiler_options())


def _compiler_options() -> Optional[Dict[str, str]]:
    """Per-jit XLA compile options from the ``xla_compiler_options`` knob
    (``RTPU_XLA_COMPILER_OPTIONS="k=v k2=v2"``). Per-jit because a TPU
    flag in ``XLA_FLAGS`` aborts the process in XLA's host-side flag
    parser ("Unknown flag in XLA_FLAGS", seen on jax 0.9.0 / libtpu
    0.0.34)."""
    from ray_tpu import config as _knobs

    raw = str(_knobs.get("xla_compiler_options") or "").strip()
    if not raw:
        return None
    out: Dict[str, Any] = {}
    for tok in raw.replace(",", " ").split():
        key, _, val = tok.partition("=")
        if not key or not val:
            raise ValueError(
                f"xla_compiler_options entry {tok!r} is not k=v")
        # Quoted values opt OUT of type coercion: string-typed XLA options
        # whose value LOOKS numeric/bool (k='123') stay strings — the
        # coercion below would otherwise make them unexpressible
        if len(val) >= 2 and val[0] == val[-1] and val[0] in "\"'":
            out[key] = val[1:-1]
            continue
        # XLA's option setter wants typed values (a literal "true" is
        # rejected as "not a valid bool value"; same for int/float
        # fields fed strings)
        if val.lower() in ("true", "false"):
            out[key] = val.lower() == "true"
        elif val.lstrip("-").isdigit():
            out[key] = int(val)
        else:
            try:
                out[key] = float(val)
            except ValueError:
                out[key] = val
    return out


@dataclass
class TrainLoopHelper:
    """Convenience bundle most train loops need: mesh + sharded state + step.

    Used by the built-in LLM workloads (``examples/``) and by users who don't
    want to hand-roll the pjit plumbing. One call builds the mesh from
    the ScalingConfig's MeshConfig, places params, and compiles the step.
    """

    mesh: Mesh
    state: TrainState
    step_fn: Callable
    rules: ShardingRules
    _multi_step_cache: Dict[int, Callable] = field(default_factory=dict)

    @classmethod
    def create(
        cls,
        init_params_fn: Callable[[], Any],
        param_axes: Any,
        loss_fn: Callable,
        optimizer: optax.GradientTransformation,
        *,
        mesh_config: Optional[MeshConfig] = None,
        mesh: Optional[Mesh] = None,
        rules: Optional[ShardingRules] = None,
        donate: bool = True,
    ) -> "TrainLoopHelper":
        rules = rules or DEFAULT_RULES
        if mesh is None:
            mesh = make_mesh(mesh_config or MeshConfig())
        with jax.set_mesh(mesh):
            # Init params already sharded: jit the initializer with sharded
            # outputs so big models never materialize replicated.
            abstract = jax.eval_shape(init_params_fn)
            p_sh = jax.tree.map(
                lambda axes: NamedSharding(mesh, rules.spec(axes)),
                param_axes,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    a is None or isinstance(a, str) for a in x),
            )
            from ray_tpu.util.device_plane import registered_jit

            params = registered_jit(init_params_fn,
                                    name="train::init_params",
                                    component="train",
                                    out_shardings=p_sh)()
            state = create_train_state(params, optimizer)
            st_sh = state_shardings(state, param_axes, mesh, rules)
            state = jax.tree.map(
                lambda x, s: jax.device_put(x, s) if hasattr(x, "shape") else x,
                state, st_sh)
            step_fn = make_train_step(loss_fn, optimizer, donate=donate)
        return cls(mesh=mesh, state=state, step_fn=step_fn, rules=rules)

    def batch_sharding(self) -> NamedSharding:
        batch_axes = tuple(a for a in ("dcn", "dp", "fsdp")
                           if a in self.mesh.axis_names)
        return NamedSharding(self.mesh, P(batch_axes or None))

    def _check_batch(self, batch: Dict[str, jax.Array]) -> None:
        shape = dict(self.mesh.shape)
        ways = 1
        for a in ("dcn", "dp", "fsdp"):
            ways *= shape.get(a, 1)
        for k, v in batch.items():
            if hasattr(v, "shape") and v.shape and v.shape[0] % ways:
                raise ValueError(
                    f"batch[{k!r}] leading dim {v.shape[0]} does not divide "
                    f"by the data-parallel ways dcn*dp*fsdp={ways} of mesh "
                    f"{shape}; pad the batch or change the mesh")

    def run_step(self, batch: Dict[str, jax.Array]):
        self._check_batch(batch)
        bs = self.batch_sharding()
        batch = jax.tree.map(lambda x: jax.device_put(x, bs), batch)
        with jax.set_mesh(self.mesh):
            self.state, metrics = self.step_fn(self.state, batch)
        return metrics

    def save_checkpoint_async(self, path: str, *, name: str = "state"):
        """Snapshot the CURRENT train state and write it in the background
        (orbax async-checkpoint role). The device→host pull — with forced
        copies — completes before this returns, so the next ``run_steps``
        may donate/overwrite the state buffers immediately; only the disk
        write overlaps training. Call ``.wait()`` on the returned handle
        before relying on the files."""
        from ray_tpu.train.checkpoint import save_pytree_async

        return save_pytree_async(self.state, path, name=name)

    def profile_steps(self, batch: Dict[str, jax.Array], n: int,
                      logdir: str):
        """Capture an XLA device trace of ``n`` scanned steps to
        ``logdir`` (view with TensorBoard's profile plugin / xprof).

        The scaling-book loop is "annotate shardings, let XLA insert
        collectives, PROFILE, iterate" — this is the profile step, one
        call. Returns the last step's metrics. A failure before the steps
        ran raises (nothing was profiled and nothing was trained); a
        capture that fails AFTER the steps ran warns and returns their
        metrics — the optimizer steps are never applied twice."""
        import warnings

        metrics = None
        try:
            with jax.profiler.trace(logdir):
                metrics = self.run_steps(batch, n)
                # completion barrier INSIDE the trace: a dependent
                # device_get spans every scanned step
                jax.device_get(jax.tree.leaves(metrics)[0])
        except Exception as e:
            if metrics is None:
                raise
            warnings.warn(f"profiler trace failed ({e}); steps DID run, "
                          f"capture incomplete")
        return metrics

    def run_steps(self, batch: Dict[str, jax.Array], n: int):
        """Run ``n`` optimizer steps on the same batch as ONE compiled
        program (``lax.scan`` over the step body) and return the last
        step's metrics.

        One dispatch + one host read per n steps instead of per step —
        the idiomatic TPU inner loop (host round-trips never pace the
        chip). The returned loss depends on every step's params (the
        carry chains them), so a ``device_get`` of it provably spans all
        n steps."""
        fresh = n not in self._multi_step_cache
        if fresh:
            step_fn = self.step_fn

            def multi(state, batch):
                def body(s, _):
                    s2, m = step_fn(s, batch)
                    return s2, m

                state, ms = jax.lax.scan(body, state, None, length=n)
                return state, jax.tree.map(lambda a: a[-1], ms)

            from ray_tpu.util.device_plane import registered_jit

            self._multi_step_cache[n] = registered_jit(
                multi, name="train::run_steps", component="train",
                steps=n, donate_argnums=(0,),
                compiler_options=_compiler_options())
        self._check_batch(batch)
        bs = self.batch_sharding()
        batch = jax.tree.map(lambda x: jax.device_put(x, bs), batch)
        import time as _time

        t0 = _time.perf_counter()
        with jax.set_mesh(self.mesh):
            self.state, metrics = self._multi_step_cache[n](self.state, batch)
        if fresh:
            # a fresh scanned program's first call is a compile event
            # (timing includes its first execution — dispatch is async so
            # compile dominates); telemetry must never break the step
            try:
                from ray_tpu.train import telemetry

                telemetry.record_compile(_time.perf_counter() - t0)
            except Exception:
                pass
        return metrics
