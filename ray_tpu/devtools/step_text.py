"""The serve step program's lowered text, hashed: the yardstick for "this
change leaves that configuration's program alone".

Usage::

    python -m ray_tpu.devtools.step_text                  # the serve presets
        [--preset NAME]... [--config benchmark/configs/X.json]...
        [--dump DIR]

For each target one JSON line: its name, the backend the text was lowered
for, the text's size and the sha256 of ``jax.jit(engine._raw_step_paged,
donate_argnums=(1,)).lower(<abstract arguments>).as_text()`` with locations
stripped. Parameters and cache are ``ShapeDtypeStruct``s, so a benchmark
cell's full configuration lowers without its weights being made; ``--dump``
also writes each text, for the diff of a pair that differs. The kernels'
forms are chosen by backend (``ops/*::impl_for``): run it on the chip for
the programs the cells run, here for the CPU's.

A preset lowers twice: at the toy engine of the tests (2 slots, 32
positions, blocks and chunks of 4: no wider than ``STEP_BUDGET``, the step
without a budget) and, as ``NAME@20x32``, at a grid of 640 positions (the
budget's three widths). A configuration file lowers at its own ``engine``
block.
Two trees are compared by running this file on each.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import sys
from typing import Any, Dict, Optional

#: every layout of ``models.layouts`` (the uniform decoder three ways: plain,
#: one window for every layer, a sparse-attention indexer and experts; the
#: windowed MoE layout two ways: Trinity's layer, and SmallThinker's with its
#: route made ahead of the attention)
SERVE_PRESETS = ("llama-debug", "mistral-debug", "sparse-moe-debug",
                 "hybrid-state-debug", "parallel-hybrid-debug",
                 "linear-hybrid-debug", "latent-moe-debug",
                 "windowed-moe-debug", "smallthinker-debug")

_TOY_ENGINES = {"": {"max_slots": 2, "max_len": 32, "block_size": 4,
                     "prefill_chunk": 4},
                "@20x32": {"max_slots": 20, "max_len": 64, "block_size": 4,
                           "prefill_chunk": 32}}


def kernels_as_text(text: str) -> str:
    """A Pallas kernel travels in a TPU lowering as serialised MLIR (base64,
    in its custom call's ``backend_config``) with the path and line of every
    operation's source in it: each body is put back as its own text, printed
    without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def as_text(match):
        module = ir.Module.parse(base64.b64decode(match.group(2)))
        asm = module.operation.get_asm(enable_debug_info=False)
        return f"{match.group(1)}\n{asm}{match.group(3)}"

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True      # (``stable_mosaic.*``)
    with ctx:
        return re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)',
                      as_text, text)


def lowered_text(fn, args) -> str:
    """``fn``'s lowering for ``args`` with the cache donated, without what
    moves when a line of source does: locations (the kernels' own too) and
    the module's name."""
    import jax

    out = jax.jit(fn, donate_argnums=(1,)).lower(*args).as_text()
    out = re.sub(r"loc\(.*?\)|#loc\d*( = .*)?", "", kernels_as_text(out))
    return re.sub(r"@\w+", "@f", out, count=1)


def step_args(config, *, max_slots: int, max_len: int, block_size: int,
              prefill_chunk: int, num_blocks: Optional[int] = None,
              window_blocks: Optional[int] = None, **_):
    """The step program's seven arguments as shapes, as ``LLMEngine`` sizes
    them for these settings."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import models

    layout = models.layout_of(config)
    width = -(-max_len // block_size)
    nb = int(num_blocks or max_slots * width)
    pools = {}
    if layout.window_pool:
        win_width = layout.table_width(config.sliding_window, prefill_chunk,
                                       block_size)
        width += win_width
        pools["window_blocks"] = int(window_blocks or max_slots * win_width)
    if layout.stateful:
        pools["state_slots"] = max_slots
    params = jax.eval_shape(
        lambda: models.init_params(jax.random.PRNGKey(0), config))
    cache = jax.eval_shape(
        lambda: models.init_cache_paged(config, nb, block_size, **pools))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return (params, cache, i32(max_slots, prefill_chunk),
            i32(max_slots, width), i32(max_slots), i32(max_slots),
            jax.ShapeDtypeStruct((max_slots,), jnp.bool_))


def step_text(config, **engine) -> str:
    """The text of the step program an ``LLMEngine(config, **engine)`` jits
    (its ``_raw_step_paged``: no engine is built)."""
    from ray_tpu.serve.llm import LLMEngine

    eng = object.__new__(LLMEngine)
    eng.config = config
    return lowered_text(eng._raw_step_paged, step_args(config, **engine))


def config_of_file(path: str):
    """``(TransformerConfig, engine settings)`` of a benchmark
    configuration file, by the benchmark's own reading of it."""
    from benchmark import weights
    from benchmark.kinds.serve_family_replica import family_path, load_family

    with open(path) as f:
        cf = json.load(f)
    make = load_family(cf).transformer_config \
        if os.path.exists(family_path(cf["reference"])) \
        else weights.transformer_config
    return make(cf), cf["engine"]


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m ray_tpu.devtools.step_text", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--preset", action="append", default=[],
                   help="a preset of models/config.py (default: the seven "
                        "serve presets)")
    p.add_argument("--config", action="append", default=[],
                   help="a benchmark configuration file")
    p.add_argument("--dump", help="directory to write each text to")
    ns = p.parse_args(argv)

    import jax

    from ray_tpu import models

    targets: Dict[str, Any] = {}
    for name in ns.preset or (() if ns.config else SERVE_PRESETS):
        for grid, engine in _TOY_ENGINES.items():
            targets[name + grid] = (models.get_config(name), engine)
    for path in ns.config:
        targets[os.path.basename(path).removesuffix(".json")] = \
            config_of_file(path)
    for name, (config, engine) in targets.items():
        text = step_text(config, **engine)
        if ns.dump:
            os.makedirs(ns.dump, exist_ok=True)
            with open(os.path.join(ns.dump, name + ".txt"), "w") as f:
                f.write(text)
        print(json.dumps({
            "name": name, "backend": jax.default_backend(),
            "bytes": len(text),
            "sha256": hashlib.sha256(text.encode()).hexdigest()}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
