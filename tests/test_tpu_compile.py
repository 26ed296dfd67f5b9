"""Compile the main path's programs for a DESCRIBED v5e 2x2 — no chip.

The TPU compiler is installed here and compiles for a topology that is
described, not attached, so these tests refuse what the chip's compiler
would refuse (tiling, VMEM, HBM fit, a Mosaic kernel GSPMD cannot
partition) at no chip time. Nothing runs: a pass says the program
compiles, not that it is right or fast.

Only one process may hold libtpu: the topology is described inside a
module-scoped fixture (never at import), everything compiles in the
test's own process, and all such tests live in this one file.
"""

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from ray_tpu import models
from ray_tpu.models import transformer
from ray_tpu.models.hybrid import window_table_width
from ray_tpu.ops.attention import flash_attention, \
    set_default_attention_impl
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.serve.llm import STEP_BUDGET
from ray_tpu.train.train_state import (create_train_state, make_train_step,
                                       state_shardings)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas():
    set_default_attention_impl("pallas")
    yield
    set_default_attention_impl(None)


def _spec(tree, sharding):
    """ShapeDtypeStructs of ``tree`` placed by ``sharding`` (one sharding,
    or a matching pytree of them)."""
    if not isinstance(sharding, (dict, list, tuple)):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding), tree)
    return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=s), tree, sharding)


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _instructions(text):
    """(computation, name, type, dims, operation) of every instruction of a
    compiled module's text: ``("%fused_computation.3", "copy.5", "bf16",
    "1,4096,14336", "copy")``; the entry computation is ``"entry"``."""
    line = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                      r"([\w\-]+)\(")
    where = ""
    for text_line in text.splitlines():
        if text_line.startswith(("%", "ENTRY ")):
            where = "entry" if text_line.startswith("ENTRY") \
                else text_line.split(" ")[0]
        m = line.match(text_line)
        if m:
            yield (where, *m.groups())


def _pool_moves(text, pool):
    """(entry | inner, instruction) of every ``copy``, ``pad``,
    ``dynamic-slice`` or ``dynamic-update-slice`` of the compiled text, or
    fusion named after one, whose result has the shape of the stacked
    ``pool`` ``[L, n_blocks, bs, ...]``, of one layer's slice of it or of
    their views flattened over layers, blocks or tokens (a narrow last axis
    also padded to the 128 lanes). The scatters that write the step's rows
    in place have that shape too and are not moves."""
    n_layers, nb, bs, *rest = pool.shape
    padded = rest[:-1] + [-(-rest[-1] // 128) * 128]
    shapes = {",".join(map(str, lead + tail))
              for tail in (rest, padded)
              for lead in ([n_layers, nb, bs], [1, nb, bs], [nb, bs],
                           [n_layers * nb, bs], [n_layers * nb * bs],
                           [nb * bs], [1, nb * bs])}
    return [("entry" if where == "entry" else "inner", name)
            for where, name, _, dims, op in _instructions(text)
            if dims in shapes and (
                op.split("-start")[0].split("-done")[0] in (
                    "copy", "pad", "slice", "dynamic-slice",
                    "dynamic-update-slice")
                or op == "fusion" and re.search("copy|slice|pad", name))]


def _materialised(text, shapes):
    """(computation, instruction) of every ``copy``, ``slice`` or
    ``dynamic-slice`` of the compiled text, or fusion named after one, that
    WRITES an array of one of ``shapes`` (``"bf16[4096,14336]"``, with a
    leading 1 or without): a slice inside the fused computation that
    multiplies by it is what a weight should be, one outside it is a copy
    of the weight."""
    want = set(shapes) | {s.replace("[", "[1,") for s in shapes}
    return [(where, name)
            for where, name, kind, dims, op in _instructions(text)
            if f"{kind}[{dims}]" in want and "fused_computation" not in where
            and (op in ("copy", "slice", "dynamic-slice")
                 or op == "fusion" and re.search("copy|slice", name))]


def _expert_kernel_holds(text, stacks, pairs, f):
    """The routed experts of a compiled step run in ``expert_mlp_fwd``:
    no grouped matmul of XLA's is left, no layer's experts are sliced out of
    their ``stacks`` (``"bf16[4,32,3072,3072]"``, a layer's as ``[1, ...]``
    or without the layer), and no float32 ``gate`` / ``up`` of ``pairs x f``
    is written for any of the step's widths."""
    assert "expert_mlp_fwd" in text and "ragged-dot" not in text
    a_layers = [re.sub(r"\[\d+,", "[", s) for s in stacks]
    assert _materialised(text, list(stacks) + a_layers) == []
    for s in a_layers:
        assert s.replace("[", "[1,") not in text, s
    for n in pairs:
        assert f"f32[{n},{f}]" not in text, (n, f)


def _pool_bytes(cache) -> int:
    return sum(math.prod(p.shape) * p.dtype.itemsize for p in cache.values())


# -- the flash kernels at real widths ---------------------------------------

@pytest.mark.parametrize("head_dim,seq,window,softcap", [
    (64, 2048, None, 0.0),
    (128, 2048, None, 0.0),
    (128, 4096, 1024, 0.0),     # banded sliding-window variant
    (128, 2048, None, 50.0),    # attention-logit softcap variant
], ids=["d64", "d128", "window1024", "softcap"])
def test_flash_fwd_bwd_compiles(one_chip, head_dim, seq, window, softcap):
    """Forward and both backward kernels (dKV, dQ), GQA 16/8 heads."""
    q = jax.ShapeDtypeStruct((2, seq, 16, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, seq, 8, head_dim), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas",
                               window=window, softcap=softcap
                               ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))
                       ).lower(q, kv, kv).compile()
    assert _n_kernels(compiled) >= 3    # fwd + dkv + dq


# -- the serve path: paged decode step and a prefill chunk ------------------

@pytest.mark.parametrize("chunk", [1, 64], ids=["decode", "prefill_chunk"])
def test_paged_step_llama_1b_compiles(one_chip, pallas, monkeypatch, chunk):
    """``decode_step_paged`` at llama_1b's full width (2 layers), bf16
    weights under float32 activations, 8 slots x 2048 context over a
    1024 x 16-token pool — the shapes ``chip_smoke.py`` serves with. A dense
    decoder's step never traces the expert layer or its kernel."""
    def never(*a, **kw):
        raise AssertionError("a dense step traced moe_layer_dropless")
    monkeypatch.setattr(models.transformer, "moe_layer_dropless", never)
    config = models.llama_1b().replace(n_layers=2, param_dtype="bfloat16",
                                       dtype="float32")
    slots, max_len, bs, nb = 8, 2048, 16, 1024
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs)), one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged,
                                     config=config), donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, max_len // bs)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert "expert_mlp_fwd" not in text and "moe_experts" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


#: the benchmark's two dense serve configurations at their published widths
#: and at the cells' own depth: (config keywords, layers, max_len, pool
#: blocks). Commit 7531257, whose layer scan took the pools as scanned inputs
#: and outputs, compiled these shapes with the same compiler to 3,453,074,432
#: and 2,656,850,944 bytes of temporaries (a second pool: 3,221,225,472 and
#: 2,415,919,104), and two layers deep to 634,598,912 and 643,843,584.
_SERVE_CELLS = {
    "mistral_7b": (dict(vocab_size=32000, d_model=4096, n_heads=32,
                        n_kv_heads=8, head_dim=128, d_ff=14336,
                        max_seq_len=32768, sliding_window=4096,
                        rope_theta=1e4, norm_eps=1e-5),
                   16, 2048, 3072),
    "qwen2_7b": (dict(vocab_size=152064, d_model=3584, n_heads=28,
                      n_kv_heads=4, head_dim=128, d_ff=18944,
                      max_seq_len=131072, rope_theta=1e6, norm_eps=1e-6,
                      attn_qkv_bias=True),
                 12, 4096, 6144),
}


def _branch_counts(jaxpr):
    """Branches of every ``cond`` of ``jaxpr``, those inside its scans'
    and conditionals' own bodies too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            yield len(eqn.params["branches"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _branch_counts(sub)


@pytest.mark.parametrize("model", ["mistral-debug", "qwen2-debug"])
def test_a_second_width_only_where_the_grid_is_wider(model):
    """The step program chooses between ``STEP_BUDGET`` positions and the
    grid where the grid is at most twice the budget (the mistral and qwen2
    cells: 16 x 32 = 512, the two-branch conditional it always was, which
    the test below counts in the compiled text too), and among three widths
    where it is wider (every other cell): each position-wise stage then has
    the second width as a branch of its own. Traced only: no device."""
    config = models.get_config(model)
    params = jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(
        models.init_cache_paged, config, 8, 16))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    step = functools.partial(models.decode_step_paged, config=config,
                             budget=STEP_BUDGET)

    def branches(slots, chunk):
        assert slots * chunk > STEP_BUDGET
        return set(_branch_counts(jax.make_jaxpr(step)(
            params, cache, i32((slots, chunk)), i32((slots, 4)),
            i32((slots,)), i32((slots,))).jaxpr))

    assert models.transformer.step_widths(STEP_BUDGET, 512) == [256, 512]
    assert branches(16, 32) == {2}
    assert models.transformer.step_widths(STEP_BUDGET, 513) == [
        256, 512, 513]
    assert branches(24, 32) == branches(32, 64) == {3}


@pytest.mark.parametrize("cell", list(_SERVE_CELLS))
def test_paged_step_holds_the_attention_kernel(one_chip, pallas, cell):
    """``decode_step_paged`` as the benchmark's cells run it (bf16, 16
    slots, chunk 32, tables 128 and 256 wide, the cells' pools and depth,
    the engine's budget: each position-wise stage a ``conditional`` between
    256 positions and all 512) with the paged-attention kernel in it: one
    Mosaic call in the scanned layer body and no array as wide as the
    expanded or float32 table. The pools are the loop's carry: nothing of a
    pool's, a layer slice's or their flattened views' shape is copied,
    sliced or updated, the donated cache is the output's buffer, and the
    temporaries, which held a second pool, are under 16 MB. The MLP's
    matrices and ``wo`` are sliced inside the fusions that multiply by
    them, in both branches: none crosses a branch's boundary as a copy."""
    kw, n_layers, max_len, nb = _SERVE_CELLS[cell]
    config = models.TransformerConfig(
        n_layers=n_layers, mlp="swiglu", norm="rms", positions="rope",
        tie_embeddings=False, dtype="bfloat16", param_dtype="bfloat16", **kw)
    slots, chunk, bs = 16, 32, 16
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs)), one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged,
                                     config=config, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, max_len // bs)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    h, kvh = config.n_heads, config.kv_heads
    assert _n_kernels(compiled) == 1        # the scan body is compiled once
    assert STEP_BUDGET < slots * chunk and text.count(" conditional(") == 2
    d, f = config.d_model, config.d_ff
    assert _materialised(text, [f"bf16[{d},{f}]", f"bf16[{f},{d}]",
                                f"bf16[{h},128,{d}]", f"bf16[{h * 128},{d}]"]
                         ) == []
    assert "paged_attention_fwd" in text
    for gone in (f"[{slots},{max_len},{kvh},{h // kvh},128]",   # repeat_kv
                 f"f32[{slots},{h},{chunk},{max_len}]",          # scores
                 f"[{slots},{max_len},{kvh},128]"):              # the gather
        assert gone not in text, gone
    for pool in cache.values():
        assert _pool_moves(text, pool) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    assert mem.temp_size_in_bytes < 16e6


def test_paged_step_sparse_moe_compiles_at_published_widths(one_chip, pallas):
    """``decode_step_paged`` at Keye-VL-2.0-30B-A3B's widths as the
    benchmark's cell runs it (bf16, 8 slots, chunk 128, a 2048-wide table
    over 14336 blocks, six layers): the three pools go in and come out,
    rows of at most ``topk`` keys keep the paged-attention kernel (1024
    query rows a slot fit its VMEM), the routed experts run in the kernel
    that walks the experts hit (``expert_mlp_fwd``: 2048 x 768 is whole
    tiles) over the WHOLE stacks (no 384 MB slice of a layer's experts, no
    float32 ``gate`` or ``up`` of the step's pairs). The pools are the
    loop's carry: nothing of K's, V's or the indexer keys' shapes is copied,
    padded, sliced or updated. The indexer's keys, 64 wide, are STORED two
    a lane row (``[6, 14336, 8, 128]``), which is the shape the loop
    carries and the step scatters whole rows into: no pass over their stack
    before, inside or after the loop (a ``[.., 64]`` stack the TPU stores
    with the block axis innermost: it was turned row-major and padded to
    ``bf16[86016,16,128]`` before the loop and cut back after it, three
    passes). A sparse row's last query is scored by the kernel that walks
    its table (``indexer_scores_fwd``): no ``[8 x 2048]``-block gather of
    the keys and no float32 products of all eight rows. The donated cache
    is the output's buffer and the temporaries (4,399,152,640 bytes at
    commit 7531257, under 1 GB with the padded copy) are under 0.2 GB."""
    config = models.TransformerConfig(
        vocab_size=151936, d_model=2048, n_layers=6, n_heads=32,
        n_kv_heads=4, head_dim=128, d_ff=768, max_seq_len=262144,
        rope_theta=1e7, norm_eps=1e-6, qk_norm=True, num_experts=128,
        expert_top_k=8, expert_norm_topk=True, index_heads=16,
        index_head_dim=64, index_topk=2048, dtype="bfloat16",
        param_dtype="bfloat16")
    slots, chunk, bs, nb, max_len = 8, 128, 16, 14336, 32768
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs)), one_chip)
    assert set(cache) == {"k", "v", "ki"}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, max_len // bs)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert "paged_attention_fwd" in text
    assert text.count(" custom-call(") >= 5
    _expert_kernel_holds(text, ["bf16[6,128,2048,768]", "bf16[6,128,768,2048]"],
                         pairs=(2048, 4096, 8192), f=768)
    # the two position-wise stages between 256 positions and all 1024,
    # beside the two ``conditional``s of a chunk row's attention
    assert text.count(" conditional(") == 2 + 2
    assert _materialised(text, ["bf16[32,128,2048]", "bf16[4096,2048]"]) == []
    assert _pool_moves(text, cache["k"]) == []
    assert _pool_moves(text, cache["v"]) == []
    assert cache["ki"].shape == (6, nb, 8, 128)
    assert _pool_moves(text, cache["ki"]) == []
    assert "indexer_scores_fwd" in text
    for gone in ("bf16[86016,16,128]",      # the stack padded to the lanes
                 "bf16[6,14336,16,64]",     # ... and turned around
                 "bf16[16384,16,64]", "bf16[16384,8,128]",  # the gather
                 "f32[8,16,32768]"):        # every row's float32 products
        assert gone not in text, gone
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    assert mem.temp_size_in_bytes < 0.2e9


def test_paged_step_hybrid_state_compiles_at_published_widths(one_chip,
                                                              pallas):
    """``decode_step_paged`` at Phi-4-mini-flash-reasoning's widths as the
    benchmark's cell runs it (bf16, all 32 layers, 32 slots, chunk 32, a
    256-wide table of the full layer's pool and a 35-wide one of the window
    pools, float32 state): five kinds of layer in three scanned segments,
    not 32 unrolled layers, each attention layer's body with the kernel
    that reads its pools through the table in it (no row's table gathered
    to ``max_len``, no float32 scores over its 4096 positions); the
    position-wise stages between 256 positions and all 1024 index their
    weights inside the branch (no matrix of an MLP, a mixer or the head is
    copied); the KV pools of both kinds are the loops' carry and take the
    step's rows in place; the donated cache is the output's buffer;
    arguments (9.22 GB: 7.7 GB of weights and the pools) and temporaries
    (0.053 GB at this commit; 1.85 GB with the ``jax.numpy`` form: the
    shared pool's gathered context 0.67 GB of it, its relaid copies as
    much again, the scores and the padded output) fit the chip."""
    from ray_tpu.models.hybrid import window_table_width

    config = models.TransformerConfig(
        vocab_size=200064, d_model=2560, n_layers=32, n_heads=40,
        n_kv_heads=20, head_dim=64, d_ff=10240, max_seq_len=262144,
        norm="layer", positions="none", norm_eps=1e-5, tie_embeddings=True,
        sliding_window=512, dtype="bfloat16", param_dtype="bfloat16",
        layer_kinds=("mamba", "window") * 8 + ("mamba", "full")
        + ("gmu", "cross") * 7)
    slots, chunk, bs, nb, max_len = 32, 32, 16, 8192, 4096
    m_win = window_table_width(512, chunk, bs)
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs,
        window_blocks=slots * m_win, state_slots=slots)), one_chip)
    assert set(cache) == {"k", "v", "wk", "wv", "conv", "ssm"}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)),
        i32((slots, max_len // bs + m_win)), i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    # two scanned segments and the scan's loop in the state-space layers of
    # each segment, the attention's loops inside the kernel: not 32
    assert 4 <= text.count(" while(") <= 12
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "diff_attention_fwd" in line]
    assert len(kernels) == 3        # window, full and cross layer bodies
    assert not re.search(r"f32\[[\d,]*4096[\d,]*\]", text)
    for gone in ("bf16[32,4096,1280]", "bf16[32,256,16,1280]",
                 "bf16[32,560,1280]", "bf16[32,35,16,1280]"):
        assert gone not in text, gone
    assert _materialised(text, [
        "bf16[2560,10240]", "bf16[10240,2560]", "bf16[2560,5120]",
        "bf16[5120,2560]", "bf16[2560,2560]", "bf16[2560,1280]",
        "bf16[200064,2560]"]) == []
    for pool in ("k", "v", "wk", "wv"):
        assert _pool_moves(text, cache[pool]) == [], pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 10.5e9
    assert mem.temp_size_in_bytes < 0.3e9


def test_paged_step_parallel_hybrid_compiles_at_published_widths(one_chip,
                                                                pallas):
    """``decode_step_paged`` at Falcon-H1-34B-Instruct's widths as the
    benchmark's cell runs it (bf16, 6 of 72 layers, 48 slots, chunk 32, a
    128-wide table, float32 state): ONE scanned period and the block form's
    loop inside it, not six unrolled layers; the attention is the uniform
    decoders' kernel (``paged_attention_fwd``) reading the pools through the
    table; the rows that feed one position take their turn of the recurrence
    in ``ssd_step_fwd``, which writes the state pool in place; the 1.2 GB
    state pool and the KV pools are the loop's carry and
    take the step's rows in place (no copy of the pool, of a layer's 201 MB
    share of it, or of a whole stack of weights: the in-projection travels
    as three lane-aligned column blocks, as one 9248-wide matrix its stack
    was copied into a padded layout every step, 568 MB); the donated cache
    is the output's buffer; arguments (12.54 GB: 10.51 GB of weights, 0.81
    of KV, 1.23 of state) and temporaries (0.10 GB) fit the chip beside the
    check's reference."""
    config = models.TransformerConfig(
        vocab_size=261120, d_model=5120, n_layers=6, n_heads=20,
        n_kv_heads=4, head_dim=128, d_ff=21504, norm_eps=1e-5,
        rope_theta=1e11, max_seq_len=262144, layer_kinds=("parallel",) * 6,
        ssm_width=4096, ssm_heads=32, ssm_head_dim=128, ssm_groups=2,
        ssm_state=256, ssm_conv=4, ssm_chunk=128,
        embedding_multiplier=5.656854249492381, lm_head_multiplier=0.0078125,
        attention_out_multiplier=0.0375,
        key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
        dtype="bfloat16", param_dtype="bfloat16")
    slots, chunk, bs, nb, max_len = 48, 32, 16, 4096, 2048
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs, state_slots=slots)),
        one_chip)
    assert set(cache) == {"k", "v", "conv", "ssm"}
    assert cache["ssm"].shape == (6, 48, 32, 128, 256)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, max_len // bs)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2       # the layers, the block rows
    kernels = sorted(
        re.search(r"%(\w+?)[.\d]* = ", line).group(1)
        for line in text.splitlines() if "tpu_custom_call" in line)
    assert kernels == ["paged_attention_fwd", "ssd_step_fwd"]
    # no whole stack of weights, no pool and no layer's states copied
    assert _materialised(text, [
        "bf16[6,5120,21504]", "bf16[6,21504,5120]", "bf16[6,5120,4096]",
        "bf16[6,5120,5120]", "bf16[6,4096,5120]", "bf16[6,5120,2560]",
        "bf16[5120,21504]", "bf16[21504,5120]", "bf16[5120,261120]",
        "f32[48,32,128,256]"]) == []
    # the rows that feed one position take their turn in the kernel, which
    # writes the state pool IN PLACE: nothing has a whole layer's states for
    # its result (the pass over all 48 slots is gone, and its read-out with
    # it), and the pool (the layers' carry and the block rows' before the
    # kernel) is never copied: the alias held
    written = [(name, op) for _, name, kind, dims, op in _instructions(text)
               if kind == "f32" and (
                   dims in ("48,32,128,256", "1,48,32,128,256")
                   or dims == "288,32,128,256" and op.split("-")[0] == "copy")]
    assert written == []
    # (the KV pools are written by dynamic-update-slice fusions whose
    # result has their shape: in place, as the temporaries' bound shows)
    for pool in ("k", "v"):
        assert _pool_moves(text, cache[pool]) == [], pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    assert 12.5e9 < mem.argument_size_in_bytes < 12.6e9
    # (0.099 GB, as before the kernel: the kernel's operands are a row's
    # few vectors and its buffers are VMEM)
    assert mem.temp_size_in_bytes < 0.12e9


def test_paged_step_latent_moe_compiles_at_published_widths(one_chip,
                                                            pallas):
    """``decode_step_paged`` at Kimi-K2.5's widths as the benchmark's cell
    runs it (bf16; the leading dense layer and four expert layers that hold
    12 of 384 sigmoid-routed experts beside a shared expert; 12 slots, chunk
    128, a table 2688 wide over 32,768 blocks of the ONE latent pool, 640
    lanes a token): two scanned segments, not five unrolled layers, each
    with the kernel that walks the latent pool's live blocks in it (no row's
    table gathered, no float32 scores over its 43,008 positions); the
    position-wise stages index their weights inside the branch (no matrix
    of the dense MLP, the shared expert, an expert stack or the projections
    but ``wq_b`` is copied); the pool is the loops' carry, takes the step's rows in place
    and is the output's buffer; arguments (10.35 GB: 6.99 GB of weights and
    the pool's 3.36) and temporaries (1.06 GB at this commit) fit the chip.
    A pool DECLARED ``kv_lora_rank + qk_rope_head_dim`` = 576 wide compiles
    to 4.70 GB of temporaries (the compiler copies it whole inside every
    step): ``pool_width`` is why it is 640."""
    config = models.TransformerConfig(
        vocab_size=20480, d_model=7168, n_layers=5, n_heads=64, d_ff=18432,
        max_seq_len=262144, norm_eps=1e-5, rope_theta=50000.0,
        tie_embeddings=False, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_factor=64.0, rope_original_len=4096, rope_mscale_all_dim=1.0,
        dense_layers=1, d_ff_expert=2048, shared_experts=1, num_experts=384,
        expert_top_k=8, expert_norm_topk=True, expert_scoring="sigmoid",
        expert_scale=2.827, experts_held=12, remat=False,
        dtype="bfloat16", param_dtype="bfloat16")
    slots, chunk, bs, nb, max_len = 12, 128, 16, 32768, 43008
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs)), one_chip)
    assert {k: v.shape for k, v in cache.items()} == {
        "kv": (5, nb, bs, 640)}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, max_len // bs)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    # two scanned segments (the leading one, a single layer, compiles to
    # its body), the attention's loops inside the kernel: not five
    # unrolled layers
    assert 1 <= text.count(" while(") <= 2
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "latent_attention_fwd" in line]
    assert len(kernels) == 2                # one a segment's layer body
    assert not re.search(r"f32\[[\d,]*43008[\d,]*\]", text)
    for gone in ("bf16[2688,16,640]", "bf16[43008,640]",    # a row's table
                 "bf16[8,128,8,512]"):              # the head groups' stack
        assert gone not in text, gone
    # (``wq_b [1536, 12288]`` IS sliced out and relaid for its 64 heads of
    # 192, 38 MB a layer, as the dense decoders' ``wq`` is: PERF.md)
    assert _materialised(text, [
        "bf16[7168,1536]", "bf16[7168,576]", "bf16[64,128,512]",
        "bf16[8192,7168]", "bf16[7168,18432]", "bf16[18432,7168]",
        "bf16[7168,2048]", "bf16[2048,7168]", "bf16[12,7168,2048]",
        "bf16[12,2048,7168]", "bf16[7168,20480]"]) == []
    _expert_kernel_holds(text, ["bf16[4,12,7168,2048]", "bf16[4,12,2048,7168]"],
                         pairs=(2048, 4096, 12288), f=2048)
    assert _pool_moves(text, cache["kv"]) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache) == 3_355_443_200
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.0e9
    # 1.057 GB at this commit: the queries and the attention's result at
    # the grid's 1536 positions, in the stream's order and the rows'
    assert mem.temp_size_in_bytes < 1.16e9


# -- the train path: one chip, and a 4-device mesh --------------------------

def _compile_train_step(topo, mesh_config, n_devices, batch, seq=2048):
    config = models.llama_1b().replace(n_layers=2, loss_chunk=512)
    mesh = make_mesh(mesh_config, devices=topo.devices[:n_devices])
    opt = optax.adamw(1e-4)
    abstract = jax.eval_shape(lambda: create_train_state(
        models.init_params(jax.random.PRNGKey(0), config), opt))
    state = _spec(abstract, state_shardings(
        abstract, models.param_axes(config), mesh))
    rows = NamedSharding(mesh, P(tuple(
        a for a in ("dcn", "dp", "fsdp") if a in mesh.axis_names) or None))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=rows)
    step = make_train_step(
        lambda p, b: models.loss_and_metrics(p, b, config), opt)
    with jax.set_mesh(mesh):
        return step.lower(state, {"inputs": tokens,
                                  "targets": tokens}).compile()


def test_train_step_one_chip_compiles(topo, pallas):
    compiled = _compile_train_step(topo, MeshConfig(fsdp=-1), 1, batch=2)
    assert _n_kernels(compiled) >= 3
    text = compiled.as_text()
    assert "expert_mlp_fwd" not in text and "moe_experts" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9


@pytest.mark.parametrize("mesh_config", [
    pytest.param(MeshConfig(fsdp=4), marks=pytest.mark.slow, id="fsdp4"),
    pytest.param(MeshConfig(fsdp=2, tp=2), id="fsdp2_tp2"),
])
def test_train_step_four_chips_holds_the_kernel(topo, pallas, mesh_config):
    """GSPMD cannot partition a Mosaic kernel; ``_attention`` runs it per
    shard under ``shard_map``. Without that this compile fails in a second
    with "Mosaic kernels cannot be automatically partitioned"."""
    compiled = _compile_train_step(topo, mesh_config, 4, batch=4)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow   # two ~15 s compiles; the kernel-holding meshes stay tier-1
def test_train_step_ring_and_halo_compile(topo, pallas):
    """The sequence-parallel paths (ring attention; halo exchange for a
    sliding window) on tp=2 x sp=2 — XLA-only inside their shard_map."""
    _compile_train_step(topo, MeshConfig(fsdp=1, tp=2, sp=2), 4, batch=2)
    config = models.mistral_7b().replace(
        n_layers=2, d_model=2048, n_heads=16, n_kv_heads=8, d_ff=5632,
        sliding_window=1024, loss_chunk=512)
    mesh = make_mesh(MeshConfig(fsdp=1, tp=2, sp=2), devices=topo.devices)
    params = _spec(
        jax.eval_shape(functools.partial(models.init_params, config=config),
                       jax.random.PRNGKey(0)),
        jax.tree.map(lambda axes: NamedSharding(mesh, P()),
                     models.param_axes(config),
                     is_leaf=lambda x: isinstance(x, tuple)))
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))
    with jax.set_mesh(mesh):
        jax.jit(jax.grad(lambda p, t: models.loss_and_metrics(
            p, {"inputs": t, "targets": t}, config)[0])
        ).lower(params, tokens).compile()


#: the paged-attention kernel's call in the five cells that run it: slots,
#: chunk, heads, the pool's KV heads, table width, blocks
_PAGED_CALLS = {
    "mistral_7b": (16, 32, 32, 8, 128, 3072),
    "qwen2_7b": (16, 32, 28, 4, 256, 6144),
    "falcon_h1_34b": (48, 32, 20, 4, 128, 4096),
    "trinity_large_full": (32, 64, 48, 8, 2048, 20480),
    "trinity_large_window": (32, 64, 48, 8, 262, 20480),
    "olmo_hybrid_7b": (32, 64, 32, 32, 160, 5120),
}


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, inner jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("cell", list(_PAGED_CALLS))
def test_paged_attention_call_compiles_and_asks_for_no_more_vmem(
        one_chip, pallas, cell):
    """``paged_attention_fwd`` alone at each cell's call: the two bodies (a
    token row's own tile, a chunk row's whole one placed in the kernel), the
    plan row 0 writes and the copies across rows compile for a described
    v5e. At the 32-head pool (128 KB a page) the call asks the compiler for
    nothing, as the parent's did, and its four page buffers are 4 MiB (a
    larger step there is what PR 50's first runs hung beside: D16)."""
    from ray_tpu.ops import paged_attention as pa

    slots, chunk, heads, kvh, width, blocks = _PAGED_CALLS[cell]
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    args = (bf16(slots, chunk, heads, 128), bf16(blocks, 16, kvh, 128),
            bf16(blocks, 16, kvh, 128), i32(slots, width), i32(slots),
            i32(slots), i32())
    call = jax.jit(lambda q, k, v, t, pos, nv, window: pa.paged_attention(
        q, k, v, t, pos, nv, window=window, scale=128 ** -0.5))
    traced = call.trace(*args)
    (eqn,) = _pallas_calls(traced.jaxpr.jaxpr)
    assert eqn.params["name"] == "paged_attention_fwd"
    asked = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
    pages = [a for a in eqn.params["grid_mapping"].scratch_avals
             if len(a.shape) == 5]
    page_bytes = sum(math.prod(a.shape) * 2 for a in pages)
    assert len(pages) == 2 and page_bytes <= 4 << 20
    if kvh == 32:
        assert asked is None and page_bytes == 4 << 20
    compiled = traced.lower().compile()
    assert _n_kernels(compiled) == 1
    assert "paged_attention_fwd" in compiled.as_text()


@pytest.mark.parametrize("chunk", [64, 128])
def test_paged_step_windowed_moe_compiles_at_published_widths(one_chip,
                                                              pallas, chunk):
    """``decode_step_paged`` at Trinity-Large-Preview's widths as the
    benchmark's cell runs it (bf16; a leading dense layer and four expert
    layers that hold 32 of 256 sigmoid-routed experts beside a shared one;
    layers sliding, sliding, sliding, full, sliding; 32 slots, chunk 64, a
    2048-wide full table beside a 261-wide window table; and at chunk 128,
    where a 768-row attention call has to ask for more scoped VMEM than the
    default 16 MiB): four scans of the
    ONE layer body (``transformer._layer_runs``), not five
    unrolled layers; every layer's attention is the uniform decoders' kernel
    (``paged_attention_fwd``, a group of 6 over 8 KV heads), the window
    layers' through the window table with the row's position taken in that
    table's numbering; both pools are the loops' carry and take the step's
    rows in place; no whole stack of weights and no layer's matrix is
    copied; the donated cache is the output's buffer; arguments and
    temporaries fit the chip beside the check's reference."""
    windows = (4096, 4096, 4096, 0, 4096)
    config = models.TransformerConfig(
        vocab_size=25024, d_model=3072, n_layers=5, n_heads=48, n_kv_heads=8,
        head_dim=128, d_ff=12288, norm_eps=1e-5, rope_theta=10000.0,
        max_seq_len=262144, qk_norm=True, attn_gate=True, post_norms=True,
        rope_layers="window", sliding_window=4096,
        attn_windows=windows, embedding_multiplier=3072 ** 0.5,
        dense_layers=1, d_ff_expert=3072, shared_experts=1, num_experts=256,
        expert_top_k=4, expert_norm_topk=True, expert_scoring="sigmoid",
        expert_scale=2.448, experts_held=32, experts_first=0, remat=False,
        dtype="bfloat16", param_dtype="bfloat16")
    assert config.num_params() == 4_321_903_872
    slots, bs, nb, max_len = 32, 16, 20480, 32768
    width = max_len // bs + window_table_width(4096, chunk, bs)
    assert width - max_len // bs == {64: 261, 128: 265}[chunk]
    nbw = slots * (width - max_len // bs)   # the engine's default
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs, window_blocks=nbw)),
        one_chip)
    assert {k: v.shape[:2] for k, v in cache.items()} == {
        "k": (1, nb), "v": (1, nb), "wk": (4, nbw), "wv": (4, nbw)}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, width)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    kernels = sorted(
        m.group(1) for m in (
            re.search(r"%(\w+?)[.\d]* = ", line)
            for line in text.splitlines() if "tpu_custom_call" in line) if m)
    assert set(kernels) == {"paged_attention_fwd", "expert_mlp_fwd"}
    # (D = F here: 2048 pairs x F is also the grid's positions x D)
    _expert_kernel_holds(text, ["bf16[4,32,3072,3072]"],
                         pairs=(1024, 8192), f=3072)
    # no stack of weights, no layer's matrix of one and no pool copied
    assert _materialised(text, [
        "bf16[4,32,3072,3072]", "bf16[32,3072,3072]", "bf16[4,3072,6144]",
        "bf16[3072,6144]", "bf16[6144,3072]", "bf16[3072,12288]",
        "bf16[12288,3072]", "bf16[3072,3072]", "bf16[3072,25024]",
        "bf16[4,3072,256]"]) == []
    for pool in cache:
        assert _pool_moves(text, cache[pool]) == [], pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    # 8.64 GB of weights, 1.34 + 2.19 GB of pools (2.22 at chunk 128)
    assert 12.1e9 < mem.argument_size_in_bytes < 12.3e9
    assert mem.temp_size_in_bytes < {64: 0.45e9, 128: 0.8e9}[chunk]
    print(f"windowed MoE step, chunk {chunk}: arguments "
          f"{mem.argument_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}")


@pytest.mark.parametrize("chunk", [64, 128])
def test_paged_step_preroute_moe_compiles_at_published_widths(one_chip,
                                                              pallas, chunk):
    """``decode_step_paged`` at SmallThinker-21BA3B-Instruct's widths as the
    benchmark's cell runs it (bf16; eight layers, full / sliding x 3 twice,
    each with 64 ReLU-gated experts of ``[2560, 768]`` held whole and routed
    from the layer's normed input ahead of its attention; 32 slots, a
    1024-wide full table beside the window table): four scans of the ONE
    layer body; the experts run in ``expert_mlp_fwd``, which takes the
    2560-wide row (whole lanes, two and a half ``[8, 128]`` tiles) without a
    copy or a pad of any weight; every layer's attention is
    ``paged_attention_fwd`` (a group of 7 over 4 KV heads); both pools take
    the step's rows in place; arguments and temporaries fit the chip beside
    the check's reference."""
    config = models.TransformerConfig(
        vocab_size=151936, d_model=2560, n_layers=8, n_heads=28, n_kv_heads=4,
        head_dim=128, d_ff=768, norm_eps=1e-6, rope_theta=1.5e6,
        max_seq_len=16384, rope_layers="window", sliding_window=4096,
        attn_windows=(0, 4096, 4096, 4096), d_ff_expert=768, num_experts=64,
        expert_top_k=6, expert_norm_topk=True, expert_act="relu",
        router_input="attn_norm", remat=False, dtype="bfloat16",
        param_dtype="bfloat16")
    assert config.num_params() == 3_966_937_600
    assert [(r.start, r.layers, r.windowed, r.pool_first, r.rope)
            for r in transformer._layer_runs(config)] == [
        (0, 1, False, 0, False), (1, 3, True, 0, True),
        (4, 1, False, 1, False), (5, 3, True, 3, True)]
    slots, bs, nb, max_len = 32, 16, 12288, 16384
    width = max_len // bs + window_table_width(4096, chunk, bs)
    nbw = slots * (width - max_len // bs)   # the engine's default
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs, window_blocks=nbw)),
        one_chip)
    assert {k: v.shape[:2] for k, v in cache.items()} == {
        "k": (2, nb), "v": (2, nb), "wk": (6, nbw), "wv": (6, nbw)}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, width)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    kernels = sorted(
        m.group(1) for m in (
            re.search(r"%(\w+?)[.\d]* = ", line)
            for line in text.splitlines() if "tpu_custom_call" in line) if m)
    assert set(kernels) == {"paged_attention_fwd", "expert_mlp_fwd"}
    widths = [w * 6 for w in (STEP_BUDGET, 2 * STEP_BUDGET, slots * chunk)]
    _expert_kernel_holds(text, ["bf16[8,64,2560,768]"], pairs=widths, f=768)
    # no stack of weights, no layer's matrix of one and no pool copied,
    # and no expert padded to a wider row. (The ROUTERS' stack ``bf16[8,
    # 2560, 64]``, 2.6 MB, IS copied once a run of layers: 64 columns are
    # half a lane tile and the compiler re-tiles it, wherever the router
    # reads: the same seven copies with ``router_input="mlp_norm"``.)
    assert _materialised(text, [
        "bf16[8,64,2560,768]", "bf16[64,2560,768]", "bf16[8,64,768,2560]",
        "bf16[64,768,2560]", "bf16[2560,3584]", "bf16[3584,2560]",
        "bf16[2560,512]", "bf16[2560,151936]"]) == []
    assert "bf16[8,64,3072,768]" not in text \
        and "bf16[512,3072,768]" not in text
    for pool in cache:
        assert _pool_moves(text, cache[pool]) == [], pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    # 7.93 GB of weights, 0.81 + 1.64 GB of pools (1.67 at chunk 128)
    assert 10.3e9 < mem.argument_size_in_bytes < 10.5e9
    assert mem.temp_size_in_bytes < {64: 0.6e9, 128: 1.0e9}[chunk]
    print(f"pre-routed MoE step, chunk {chunk}: arguments "
          f"{mem.argument_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}")


def test_paged_step_linear_hybrid_compiles_at_published_widths(one_chip,
                                                               pallas):
    """``decode_step_paged`` at Olmo-Hybrid-7B's widths as the benchmark's
    cell runs it (bf16, 12 of 32 layers, 32 slots, chunk 64, a 160-wide
    table, float32 state): ONE scanned period holding ONE scanned delta
    layer and the two loops of the rule's rows (the block form, the one
    turn), not twelve unrolled layers; the full layers' 30 MHA heads reach
    the uniform decoders' kernel (``paged_attention_fwd``, once in the
    program) through a pool whose head axis is 32 wide; the KV pools and the
    0.68 GB state pool are the loops' carry and take the step's rows in
    place (no copy of a pool, of a layer's share of it, or of a whole stack
    of weights); the donated cache is the output's buffer; arguments (11.24
    GB: 6.54 GB of weights, 4.03 of KV, 0.68 of state) and temporaries
    (0.38 GB) fit the chip beside the 1.69 GB of snapshots and the check's
    reference. The two programs that copy a slot's state to and from the
    snapshot pool take no temporaries to speak of."""
    from ray_tpu.serve.llm import LLMEngine

    config = models.TransformerConfig(
        vocab_size=100352, d_model=3840, n_layers=12, n_heads=30,
        head_dim=128, d_ff=11008, norm_eps=1e-6, positions="none",
        max_seq_len=65536, layer_kinds=(("delta",) * 3 + ("full",)) * 3,
        delta_key_heads=30, delta_key_dim=96, delta_value_dim=192,
        delta_conv=4, delta_neg_eigval=True, dtype="bfloat16",
        param_dtype="bfloat16")
    slots, chunk, bs, nb, max_len = 32, 64, 16, 5120, 2560
    params = _spec(jax.eval_shape(functools.partial(
        models.init_params, config=config), jax.random.PRNGKey(0)), one_chip)
    cache = _spec(jax.eval_shape(functools.partial(
        models.init_cache_paged, config, nb, bs, state_slots=slots)),
        one_chip)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (3, 5120, 16, 32, 128), "v": (3, 5120, 16, 32, 128),
        "conv": (9, 32, 34560), "delta": (9, 32, 15, 96, 384)}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = jax.jit(functools.partial(models.decode_step_paged, config=config,
                                     step_stats=True, budget=STEP_BUDGET),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, cache, i32((slots, chunk)), i32((slots, max_len // bs)),
        i32((slots,)), i32((slots,)),
        active=jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip),
    ).compile()
    text = compiled.as_text()
    # the periods, the delta layers, the block rows, the single rows
    assert text.count(" while(") == 4
    kernels = sorted(
        re.search(r"%(\w+?)[.\d]* = ", line).group(1)
        for line in text.splitlines() if "tpu_custom_call" in line)
    assert kernels == ["paged_attention_fwd"]
    # no whole stack of weights, no layer's matrix and no pool copied
    assert _materialised(text, [
        "bf16[3,3,3840,11520]", "bf16[3,3,3840,5760]", "bf16[3,3,5760,3840]",
        "bf16[3,3,3840,11008]", "bf16[3,3,11008,3840]", "bf16[3,3840,11008]",
        "bf16[3,11008,3840]", "bf16[3,3840,3840]", "bf16[3840,100352]",
        "bf16[3840,11520]", "bf16[3840,11008]", "bf16[11008,3840]"]) == []
    # the state pool is the loops' carry, written a row at a time where it
    # lies (dynamic-update-slice fusions whose result has its shape): never
    # copied, and no layer's 32 states sliced out of it
    assert [(name, op) for _, name, kind, dims, op in _instructions(text)
            if kind == "f32" and (
                dims in ("32,15,96,384", "1,32,15,96,384")
                or dims in ("288,15,96,384", "9,32,15,96,384", "9,32,34560")
                and op.split("-")[0] == "copy")] == []
    for pool in ("k", "v"):
        assert _pool_moves(text, cache[pool]) == [], pool
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == _pool_bytes(cache)
    assert 11.2e9 < mem.argument_size_in_bytes < 11.3e9
    assert mem.temp_size_in_bytes < 0.45e9
    # slot <-> snapshot pool: the state leaves alone, in place
    snaps = {k: jax.ShapeDtypeStruct((v.shape[0], 80) + v.shape[2:], v.dtype,
                                     sharding=one_chip)
             for k, v in cache.items() if k in ("conv", "delta")}
    at = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    take = jax.jit(LLMEngine._raw_snapshot, donate_argnums=(0,)).lower(
        snaps, cache, at, at).compile()
    give = jax.jit(LLMEngine._raw_restore, donate_argnums=(0,)).lower(
        cache, snaps, at, at).compile()
    assert sum(math.prod(s.shape) * 4 for s in snaps.values()) \
        == 80 * 9 * 2_350_080
    for program in (take, give):
        assert program.memory_analysis().temp_size_in_bytes < 1e6
    assert give.memory_analysis().alias_size_in_bytes == _pool_bytes(cache)
