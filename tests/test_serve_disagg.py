"""Disaggregated prefill/decode serving (ISSUE 13): KV block
gather/scatter, engine export/adopt parity, the DeviceChannel/store
transfer plane (demux, single-writer discipline, block-batch framing),
transfer-aware routing + cross-pool admission, structured error_type,
the streamed bounded-memory replay harness, and the deployed two-pool
application (round-trip, leaks, chaos at the transfer seam,
multi-node load reports)."""

import dataclasses
import os
import threading
import time

import numpy as np
import pytest


# ---------------------------------------------------------------------------
# models: block gather/scatter
# ---------------------------------------------------------------------------

def test_gather_scatter_kv_blocks_roundtrip():
    import jax.numpy as jnp

    from ray_tpu.models import gather_kv_blocks, scatter_kv_blocks

    rng = np.random.default_rng(0)
    cache = {"k": jnp.asarray(rng.normal(size=(2, 8, 4, 2, 3))
                              .astype(np.float32)),
             "v": jnp.asarray(rng.normal(size=(2, 8, 4, 2, 3))
                              .astype(np.float32))}
    got = gather_kv_blocks(cache, [5, 1, 6])
    assert np.allclose(np.asarray(got["k"]),
                       np.asarray(cache["k"])[:, [5, 1, 6]])
    # scatter into different blocks of a zero pool; out-of-range pad ids
    # are dropped (the bucketing contract)
    dst = {"k": jnp.zeros((2, 8, 4, 2, 3), jnp.float32),
           "v": jnp.zeros((2, 8, 4, 2, 3), jnp.float32)}
    pad = {"k": jnp.concatenate(
               [got["k"], jnp.ones((2, 1, 4, 2, 3), jnp.float32)], 1),
           "v": jnp.concatenate(
               [got["v"], jnp.ones((2, 1, 4, 2, 3), jnp.float32)], 1)}
    out = scatter_kv_blocks(dst, [2, 0, 7, 8], pad)   # 8 = OOB -> dropped
    assert np.allclose(np.asarray(out["k"])[:, [2, 0, 7]],
                       np.asarray(got["k"]))
    untouched = [i for i in range(8) if i not in (2, 0, 7)]
    assert np.asarray(out["k"])[:, untouched].sum() == 0


# ---------------------------------------------------------------------------
# engine: prefill-only export + adopt = token-exact disaggregation
# ---------------------------------------------------------------------------

def _f32_cfg():
    from ray_tpu import models

    return dataclasses.replace(models.get_config("llama-debug"),
                               dtype="float32", param_dtype="float32")


def _drain(eng, max_steps=500):
    for _ in range(max_steps):
        if not eng.step():
            return
    raise AssertionError("engine did not drain")


def test_engine_export_adopt_parity_and_no_leaks():
    """prefill_only on engine P + adopt on engine D == sequential
    generate, token-exact, with every block returned on both pools."""
    import jax

    from ray_tpu import models
    from ray_tpu.models import transformer as T
    from ray_tpu.serve.llm import KVExport, LLMEngine

    cfg = _f32_cfg()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).tolist() for n in (13, 5, 21)]
    refs = []
    for p in prompts:
        g = T.generate(params, jax.numpy.asarray(
            np.asarray(p, np.int32)[None]), cfg, max_new_tokens=6)
        refs.append([int(x) for x in np.asarray(g[0, len(p):])])

    P = LLMEngine(cfg, params, max_slots=4, max_len=64, block_size=4,
                  prefill_chunk=4, role="prefill")
    D = LLMEngine(cfg, params, max_slots=4, max_len=64, block_size=4,
                  prefill_chunk=4, role="decode")
    exports = []
    for p in prompts:
        sink = []
        P.submit(p, 6, sink.append, prefill_only=True)
        _drain(P)
        (e,) = [x for x in sink if isinstance(x, KVExport)]
        assert sink[-1] is None
        exports.append(e)
    outs = []
    for p, e in zip(prompts, exports):
        sink = []
        outs.append(sink)
        D.adopt(p, e.kv, e.token, 6, sink.append)
    _drain(D)
    got = [[t for t in o if t is not None] for o in outs]
    assert got == refs
    # the export's first token IS the stream's first token
    assert all(o[0] == e.token for o, e in zip(got, exports))
    for eng in (P, D):
        assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks
        assert eng.kv_state()["role"] in ("prefill", "decode")
    assert P.stats["exported"] == 3 and D.stats["adopted"] == 3


def test_an_indexers_keys_travel_in_their_stored_shape():
    """A model with a sparse-attention indexer: the prompt's blocks leave
    the prefill engine with the ``ki`` pool in its STORED shape (eight
    16-wide keys a 128-lane row: ``[L, n, 1, 128]``, not ``[L, n, bs, di]``),
    cross the wire (``pack_export`` / ``unpack_payload``), pass the adopting
    engine's geometry check (the block size is read from K) and its scatter,
    and the decode engine then speaks token for token like one engine that
    served the request whole; a payload in the old shape is refused."""
    import jax

    from ray_tpu import models
    from ray_tpu.serve.kv_transfer import pack_export, unpack_payload
    from ray_tpu.serve.llm import KVExport, LLMEngine

    cfg = dataclasses.replace(models.get_config("sparse-moe-debug"),
                              dtype="float32", param_dtype="float32")
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.random.default_rng(11).integers(0, 256, 43).tolist()
    assert len(prompt) > cfg.index_topk     # the indexer selects
    kw = dict(max_slots=2, max_len=64, block_size=8, prefill_chunk=8)

    def tokens(sink):
        return [t for t in sink if isinstance(t, int)]

    whole, sink = LLMEngine(cfg, params, **kw), []
    whole.submit(prompt, 7, sink.append)
    _drain(whole)
    want = tokens(sink)
    assert len(want) == 7

    P = LLMEngine(cfg, params, role="prefill", **kw)
    D = LLMEngine(cfg, params, role="decode", **kw)
    sink = []
    P.submit(prompt, 7, sink.append, prefill_only=True)
    _drain(P)
    (export,) = [x for x in sink if isinstance(x, KVExport)]
    n = -(-len(prompt) // 8)
    assert export.kv["ki"].shape == (cfg.n_layers, n, 1, 128)
    assert export.kv["k"].shape[1:3] == (n, 8)
    meta, arr = pack_export(export)
    kv = unpack_payload(meta, arr)
    assert {name: a.shape for name, a in kv.items()} \
        == {name: a.shape for name, a in export.kv.items()}
    with pytest.raises(ValueError, match="ki"):
        D.adopt(prompt, {**kv, "ki": kv["ki"].reshape(
            cfg.n_layers, n, 8, cfg.index_head_dim)}, export.token, 7,
            lambda item: None)
    got = []
    D.adopt(prompt, kv, export.token, 7, got.append)
    _drain(D)
    assert tokens(got) == want
    for eng in (P, D):
        assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks


def test_exports_adoptions_and_a_migration_with_a_step_in_flight():
    """The roles under the lookahead. Prefill: prompts submitted TOGETHER,
    so an export is gathered while the other rows' next step is in flight.
    Decode: requests adopted while others decode. Migration: marked with a
    step in flight, which the engine settles first, so the export ships the
    session's newest token and the KV of every token fed; the continuation
    on a third engine is token-exact."""
    import jax

    from ray_tpu import models
    from ray_tpu.models import transformer as T
    from ray_tpu.serve.llm import KVExport, LLMEngine

    cfg = _f32_cfg()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, 256, n).tolist() for n in (13, 5, 21)]
    refs = []
    for p in prompts:
        g = T.generate(params, jax.numpy.asarray(
            np.asarray(p, np.int32)[None]), cfg, max_new_tokens=12)
        refs.append([int(x) for x in np.asarray(g[0, len(p):])])
    kw = dict(max_slots=4, max_len=64, block_size=4, prefill_chunk=4)
    P, D, M = (LLMEngine(cfg, params, role=role, **kw)
               for role in ("prefill", "decode", "decode"))
    sinks = [[] for _ in prompts]
    for p, sink in zip(prompts, sinks):
        P.submit(p, 12, sink.append, prefill_only=True)
    _drain(P)
    exports = []
    for sink in sinks:
        (e,) = [x for x in sink if isinstance(x, KVExport)]
        assert sink[-1] is None
        exports.append(e)
    assert [e.token for e in exports] == [r[0] for r in refs]
    assert P.stats["steps_dispatched_ahead"] == P.stats["steps"] - 1
    outs = [[] for _ in prompts]
    for p, e, out in zip(prompts, exports, outs):
        D.adopt(p, e.kv, e.token, 12, out.append)
        D.step()
        D.step()
    assert D._inflight is not None
    # every live session is marked; the next step() settles and exports
    marked = D.begin_migration()
    assert len(marked) == 3
    D.step()
    assert D._inflight is None and D.stats["migrated_out"] == 3
    conts = []
    for (req, reply), p, out in zip(marked, prompts, outs):
        payload = reply.get_nowait()
        seen = [t for t in out if isinstance(t, int)]
        assert payload["fed_tokens"] == p + seen[:-1]
        assert payload["last_token"] == seen[-1]
        assert payload["generated"] == len(seen) < 12
        conts.append([])
        M.adopt(payload["fed_tokens"], payload["kv"], payload["last_token"],
                payload["max_new_tokens"] - payload["generated"] + 1,
                conts[-1].append)
    _drain(D)
    _drain(M)
    for out, cont, ref in zip(outs, conts, refs):
        seen = [t for t in out if isinstance(t, int)]
        # adoption re-emits the handoff token
        assert seen[:-1] + [t for t in cont if t is not None] == ref
    for eng in (P, D, M):
        assert eng.pool.free_count + len(eng.prefix) == eng.pool.num_blocks
        assert all(r is None for r in eng._slots)


def test_adopt_rejects_bad_geometry():
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(_f32_cfg(), max_slots=2, max_len=64, block_size=4,
                    role="decode")
    kv = {"k": np.zeros((2, 2, 4, 2, 16), np.float32),
          "v": np.zeros((2, 2, 4, 2, 16), np.float32)}
    with pytest.raises(ValueError, match="blocks"):
        eng.adopt(list(range(13)), kv, 7, 4, lambda t: None)  # needs 4
    with pytest.raises(ValueError, match="block_size"):
        bad = {"k": np.zeros((2, 4, 8, 2, 16), np.float32),
               "v": np.zeros((2, 4, 8, 2, 16), np.float32)}
        eng.adopt(list(range(13)), bad, 7, 4, lambda t: None)
    # nothing was claimed by the rejected adopts
    assert eng.pool.free_count == eng.pool.num_blocks


# ---------------------------------------------------------------------------
# transfer plane: pack/unpack, ring demux, single-writer under threads
# ---------------------------------------------------------------------------

def _fake_export(seed, n_blocks=3, bs=4):
    from ray_tpu.serve.llm import KVExport

    rng = np.random.default_rng(seed)
    kv = {"k": rng.normal(size=(2, n_blocks, bs, 2, 8))
          .astype(np.float32),
          "v": rng.normal(size=(2, n_blocks, bs, 2, 8))
          .astype(np.float32)}
    return KVExport(token=seed, prompt_len=n_blocks * bs - 1,
                    block_size=bs, kv=kv)


def test_pack_unpack_blocks_are_contiguous_records():
    from ray_tpu.serve.kv_transfer import pack_export, unpack_payload

    e = _fake_export(7)
    meta, arr = pack_export(e)
    assert arr.flags["C_CONTIGUOUS"] and arr.shape[0] == 3
    # one block == one contiguous record (what chunk alignment frames)
    assert meta["n_blocks"] == 3 and meta["token"] == 7
    kv = unpack_payload(meta, arr)
    assert np.array_equal(kv["k"], e.kv["k"])
    assert np.array_equal(kv["v"], e.kv["v"])


def test_kv_channel_out_of_order_demux_and_concurrent_writers(tmp_path):
    """12 payloads shipped from 8 threads (the deployed replica's
    concurrency shape) and fetched out of order by 4 threads: every
    request gets ITS payload — the per-channel writer lock keeps the
    single-writer ring sound, the request-id demux parks strays."""
    from ray_tpu.serve.kv_transfer import KVReceiver, KVSender

    e = _fake_export(1)
    snd = KVSender("srcT", max_payload_bytes=e.nbytes)
    rcv = KVReceiver()
    descs = {}
    dlock = threading.Lock()

    def ship(i):
        d = snd.ship(_fake_export(i), req_id=f"r{i}", dst_id="dstT",
                     same_host=True, timeout=30.0)
        with dlock:
            descs[i] = d

    shippers = [threading.Thread(target=ship, args=(i,))
                for i in range(12)]
    for t in shippers:
        t.start()
    got = {}
    glock = threading.Lock()
    errs = []

    def fetch(i):
        # wait for this request's descriptor, then fetch
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            with dlock:
                d = descs.get(i)
            if d is not None:
                break
            time.sleep(0.01)
        try:
            meta, kv = rcv.fetch(d, timeout=30.0)
            with glock:
                got[i] = (meta, kv)
        except BaseException as ex:  # noqa: BLE001 - surfaced below
            errs.append((i, ex))

    fetchers = [threading.Thread(target=fetch, args=(i,))
                for i in reversed(range(12))]
    for t in fetchers:
        t.start()
    for t in shippers + fetchers:
        t.join(timeout=60)
    assert not errs, errs
    assert len(got) == 12
    for i in range(12):
        meta, kv = got[i]
        ref = _fake_export(i)
        assert meta["token"] == i
        assert np.array_equal(kv["k"], ref.kv["k"])
    snd.close()
    rcv.close()


def test_kv_channel_overflow_falls_back_to_store():
    """A wedged decode side (nobody reads the ring) must not stall
    prefill: the ship times out on the full ring and degrades to the
    store path."""
    import ray_tpu
    from ray_tpu.serve.kv_transfer import KVReceiver, KVSender

    ray_tpu.init(num_cpus=2, ignore_reinit_error=True)
    try:
        e = _fake_export(2)
        snd = KVSender("srcO", max_payload_bytes=e.nbytes, slots=2)
        descs = [snd.ship(_fake_export(i), req_id=f"o{i}", dst_id="dstO",
                          same_host=True, timeout=0.2) for i in range(4)]
        kinds = [d["kind"] for d in descs]
        assert kinds[0] == "channel" and "ref" in kinds, kinds
        # and the store-path descriptor still fetches correctly
        rcv = KVReceiver()
        i = kinds.index("ref")
        meta, kv = rcv.fetch(descs[i], timeout=30)
        assert np.array_equal(kv["k"], _fake_export(i).kv["k"])
        snd.close()
        rcv.close()
    finally:
        ray_tpu.shutdown()


def test_pull_chunks_align_frames_whole_records():
    """Block-batch framing on the chunked-pull path: records start
    AFTER the serialized header (align_base), align rounds the chunk
    size down to whole records and anchors every boundary on a record
    edge, the tail still completes, and the assembled bytes are
    exact."""
    from ray_tpu.cluster.adapter import pull_chunks

    record = 48_000                       # "block" stride
    header = 1234                         # serialized pickle/pad prefix
    src = os.urandom(header + record * 21)
    offsets = []

    def call(method, oid_b, off, ln, timeout=None):
        offsets.append((off, ln))
        return src[off:off + ln]

    class W:
        def __init__(self, n):
            self.buf = bytearray(n)

        def write(self, off, data):
            self.buf[off:off + len(data)] = data

    w = W(len(src))
    assert pull_chunks(call, b"o" * 16, len(src), w, chunk=200_000,
                       parallel=3, align=record, align_base=header)
    assert bytes(w.buf) == src
    for off, ln in offsets:
        if off:                           # chunks start on RECORD edges
            assert (off - header) % record == 0
            if off + ln < len(src):
                assert ln % record == 0   # every interior chunk whole
        else:                             # first chunk: header + records
            assert (ln - header) % record == 0 or off + ln == len(src)

    # no hint (align=1): plain fixed-size chunking still exact
    offsets.clear()
    w2 = W(len(src))
    assert pull_chunks(call, b"o" * 16, len(src), w2, chunk=200_000,
                       parallel=2)
    assert bytes(w2.buf) == src


# ---------------------------------------------------------------------------
# structured errors (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

def test_task_error_carries_error_type_across_pickling():
    import cloudpickle

    from ray_tpu.core.exceptions import TaskError
    from ray_tpu.serve.admission import (DeadlineExceededError,
                                         RequestShedError)

    e = TaskError(RequestShedError("request shed (ttft): x",
                                   reason="ttft"), "tb", "t")
    e2 = cloudpickle.loads(cloudpickle.dumps(e))
    assert e2.error_type == "shed"
    assert isinstance(e2.cause, RequestShedError)
    assert e2.cause.reason == "ttft"
    assert TaskError(DeadlineExceededError("late")).error_type \
        == "deadline"

    class Unpicklable(Exception):
        def __reduce__(self):
            raise RuntimeError("nope")

    e3 = cloudpickle.loads(cloudpickle.dumps(TaskError(Unpicklable("b"))))
    assert e3.error_type == "Unpicklable" and "b" in str(e3.cause)


def test_replay_classifier_uses_error_type_not_strings():
    from _replay import classify_error
    from ray_tpu.core.exceptions import TaskError
    from ray_tpu.serve.admission import (DeadlineExceededError,
                                         RequestShedError)

    assert classify_error(RequestShedError("x")) == "shed"
    assert classify_error(DeadlineExceededError("x")) == "deadline"
    assert classify_error(
        TaskError(RequestShedError("x"), "", "")) == "shed"
    assert classify_error(
        TaskError(DeadlineExceededError("x"), "", "")) == "deadline"
    # a wrapper whose MESSAGE merely mentions the words is NOT a shed
    assert classify_error(
        RuntimeError("request shed (ttft) DeadlineExceededError")) \
        == "error"
    assert classify_error(TaskError(ValueError("boom"), "", "")) \
        == "error"


# ---------------------------------------------------------------------------
# replay harness: streamed trace, bounded stats
# ---------------------------------------------------------------------------

def test_trace_streams_and_matches_materialized():
    from _replay import TraceConfig, gen_trace, iter_trace

    cfg = TraceConfig(n_requests=64, seed=5, long_every=8,
                      long_prompt_tokens=99)
    streamed = list(iter_trace(cfg))
    assert gen_trace(cfg) == streamed          # same determinism
    longs = [r for i, r in enumerate(streamed) if (i + 1) % 8 == 0]
    assert all(len(r.prompt) == cfg.shared_prefix_tokens + 99
               for r in longs)
    shorts = [r for i, r in enumerate(streamed) if (i + 1) % 8]
    assert max(len(r.prompt) for r in shorts) \
        < cfg.shared_prefix_tokens + 99


def test_replay_bounded_reservoirs_and_classification():
    from _replay import (Request, TraceConfig, _Reservoir, iter_trace,
                         replay)
    from ray_tpu.serve.admission import RequestShedError

    r = _Reservoir(cap=100, seed=1)
    for i in range(10_000):
        r.add(float(i))
    assert len(r.xs) == 100 and r.n == 10_000
    assert 0 < r.percentile(0.5) < 10_000

    def stream(req: Request):
        if req.tenant == 0:
            raise RequestShedError("no")
        yield 1
        yield 2

    cfg = TraceConfig(n_requests=40, n_tenants=2, seed=3,
                      burst_rps=10_000.0)
    stats = replay(stream, iter_trace(cfg), time_scale=0.0,
                   max_clients=8)
    assert stats.started == 40
    assert stats.completed + stats.shed == 40 and stats.shed > 0
    assert stats.errors == 0
    assert stats.tokens == 2 * stats.completed


# ---------------------------------------------------------------------------
# router: budget admission + transfer-aware decode picking (no runtime)
# ---------------------------------------------------------------------------

class _Id:
    def __init__(self, b):
        self._b = b

    def binary(self):
        return self._b


class _Rep:
    def __init__(self, b):
        self._actor_id = _Id(b)


def _handle_with(replicas):
    from ray_tpu.serve.handle import DeploymentHandle

    h = DeploymentHandle("d")
    h._replicas = replicas
    h._version = 0
    return h


def test_disagg_budget_check_sheds_on_decode_kv():
    from ray_tpu.serve.admission import RequestShedError
    from ray_tpu.serve.disagg import DisaggHandle

    dh = DisaggHandle(_handle_with([_Rep(b"p")]),
                      _handle_with([_Rep(b"d")]))
    # seed the decode handle's own TTL'd view (the shared routing-state
    # seam _pool_loads now delegates to)
    dh.decode._route_state.update(
        kv_loads={b"d": {"kv_free": 2, "kv_total": 32,
                         "block_size": 16, "inflight": 0,
                         "ts": time.time()}},
        kv_next=time.monotonic() + 3600)
    with pytest.raises(RequestShedError) as ei:
        dh._budget_check(40, 8)           # needs 48 tokens > 2*16
    assert ei.value.reason == "decode_kv"
    assert ei.value.error_type == "shed"
    dh._budget_check(24, 8)               # 32 tokens fits exactly


def test_disagg_decode_pick_prefers_same_node_and_capacity():
    from ray_tpu.serve.disagg import DisaggHandle

    reps = [_Rep(b"a"), _Rep(b"b")]
    dh = DisaggHandle(_handle_with([_Rep(b"p")]), _handle_with(reps))
    now = time.time()
    dh.decode._route_state.update(
        kv_loads={
            b"a": {"kv_free": 0, "kv_total": 32, "inflight": 4,
                   "node": "n1", "ts": now},
            b"b": {"kv_free": 32, "kv_total": 32, "inflight": 0,
                   "node": "n2", "ts": now}},
        kv_next=time.monotonic() + 3600)
    picks = {dh._pick_decode("n2")._actor_id.binary()
             for _ in range(20)}
    assert picks == {b"b"}                # free + same-node wins
    # exclusion bars the named replica
    assert dh._pick_decode("n2", exclude=b"b")._actor_id.binary() == b"a"


# ---------------------------------------------------------------------------
# deployed two-pool application
# ---------------------------------------------------------------------------

@pytest.fixture
def rt_serve():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_disagg_deployed_roundtrip_channel_path_no_leaks(rt_serve):
    """One prefill + one decode replica on one host: requests flow
    prefill -> DeviceChannel ring -> decode, streams are token-stable
    across a repeat (decode-side trie adoption), per-pool stats count
    exports/adoptions, and both pools drain to zero leaked blocks."""
    import ray_tpu
    from ray_tpu import serve

    h = serve.deploy_disagg(
        "llama-debug", name="dsrv", prefill_replicas=1,
        decode_replicas=1, max_slots=4, max_len=96, block_size=8,
        prefill_chunk=8, seed=0)
    try:
        prompt = np.random.default_rng(0).integers(0, 256, 20).tolist()
        toks = list(h.stream(prompt, 6))
        assert len(toks) == 6
        assert list(h.stream(prompt, 6)) == toks   # deterministic repeat

        states = h.kv_states()
        assert [s["role"] for s in states["prefill"]] == ["prefill"]
        assert [s["role"] for s in states["decode"]] == ["decode"]
        for pool in states.values():
            for s in pool:
                assert s["inflight"] == 0 and s["queued"] == 0
                assert s["kv_free"] + s["prefix"]["nodes"] \
                    == s["kv_total"], s

        # per-pool engine stats: the prefill pool exported, the decode
        # pool adopted, and the transfer rode the same-host channel
        # (same node id -> ship() picked the ring)
        h.prefill._refresh(force=True)
        h.decode._refresh(force=True)
        pstats = ray_tpu.get(h.prefill._replicas[0].handle_request
                             .remote("stats", (), {}), timeout=60)
        dstats = ray_tpu.get(h.decode._replicas[0].handle_request
                             .remote("stats", (), {}), timeout=60)
        assert pstats["exported"] >= 2 and dstats["adopted"] >= 2
        # rings are session-named: the runtime shutdown sweep
        # (rtpu-chan-<session>-*) reclaims them even though replicas
        # are killed, never asked to clean up (r16 drive regression)
        import glob as _glob

        sess = ray_tpu.get_runtime_context().get_session_id()
        assert any(f"rtpu-chan-{sess}-kvx-" in p
                   for p in _glob.glob("/dev/shm/rtpu-chan-*kvx*"))
        # per-pool load reports reach the controller with roles + nodes
        from conftest import poll_until

        def role_reports():
            loads = {}
            for hd in (h.prefill, h.decode):
                loads.update(hd._pool_loads_fresh()
                             if hasattr(hd, "_pool_loads_fresh")
                             else {})
            p = h._pool_loads(h.prefill)
            d = h._pool_loads(h.decode)
            return (p and d
                    and all(v.get("role") == "prefill"
                            for v in p.values())
                    and all(v.get("role") == "decode"
                            for v in d.values()))

        poll_until(role_reports, timeout=30,
                   desc="per-pool load reports at controller")
    finally:
        h.shutdown()


def test_disagg_chaos_prefill_killed_mid_transfer_no_leaks(rt_serve,
                                                          tmp_path):
    """Failpoint at the KV-transfer seam (serve.kv_transfer): SIGKILL a
    prefill replica exactly when it would ship blocks. The router
    re-routes to the surviving prefill replica (the caller sees a
    complete stream), the decode pool adopts nothing partial, the
    controller reconciles a replacement, and ZERO KV blocks or parked
    ring payloads leak on any live replica."""
    import ray_tpu
    from conftest import poll_until
    from ray_tpu import serve
    from ray_tpu.util import failpoints

    h = serve.deploy_disagg(
        "llama-debug", name="dchaos", prefill_replicas=2,
        decode_replicas=1, max_slots=4, max_len=96, block_size=8,
        prefill_chunk=8, seed=0)
    try:
        prompt = np.random.default_rng(1).integers(0, 256, 24).tolist()
        ref = list(h.stream(prompt, 5))          # warm both paths
        failpoints.arm("serve.kv_transfer=kill"
                       f"@once={tmp_path / 'kvkill.tok'}")
        got = [list(h.stream(prompt, 5)) for _ in range(6)]
        assert all(g == ref for g in got), (ref, got)

        # the dead prefill replica was replaced
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        poll_until(
            lambda: ray_tpu.get(ctrl.list_deployments.remote())[
                "dchaos-prefill"]["num_replicas"] == 2,
            timeout=60, desc="prefill replacement reconciled")

        # zero leaks on every LIVE replica of BOTH pools
        def no_leaks():
            states = h.kv_states()
            return all(
                s["inflight"] == 0 and s["queued"] == 0
                and s["kv_free"] + s["prefix"]["nodes"] == s["kv_total"]
                for pool in states.values() for s in pool) and states

        poll_until(no_leaks, timeout=60,
                   desc="all pools drained, zero leaked KV blocks")
    finally:
        failpoints.disarm()
        h.shutdown()


# ---------------------------------------------------------------------------
# multi-node: proxy-driven load-aware routing + per-pool reports (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_multinode_proxy_routing_and_pool_reports():
    """Two extra node daemons; a deployed app spread across >= 2 nodes,
    driven through the HTTP proxy with load-aware routing (both
    replicas serve), and per-pool load reports from BOTH nodes reach
    the head controller with distinct node ids."""
    import http.client
    import json as _json

    import ray_tpu
    from conftest import poll_until
    from ray_tpu import serve
    from ray_tpu.cluster import Cluster

    c = Cluster()
    try:
        c.add_node(num_cpus=2)
        c.add_node(num_cpus=2)
        ray_tpu.init(address=c.address, cluster_authkey=c.authkey,
                     num_cpus=2)

        class Where:
            def __init__(self):
                self._n = 0

            def __call__(self, x=None):
                self._n += 1
                import ray_tpu as rt

                return rt.get_runtime_context().get_node_id()

            def load_state(self):
                import ray_tpu as rt

                return {"inflight": self._n, "kv_free": 8,
                        "kv_total": 8, "role": "proxy-pool",
                        "node": rt.get_runtime_context().get_node_id()}

        app = serve.deployment(
            Where, num_replicas=2,
            ray_actor_options={"scheduling_strategy": "SPREAD",
                               "num_cpus": 1}).bind()
        handle = serve.run(app, name="where_app",
                           route_prefix="where_app")
        proxy = serve.start_http_proxy(port=0)
        try:
            served_nodes = set()
            for _ in range(12):
                conn = http.client.HTTPConnection(
                    "127.0.0.1", proxy.port, timeout=60)
                body = _json.dumps(1)
                conn.request("POST", "/where_app", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                assert resp.status == 200, resp.status
                served_nodes.add(
                    _json.loads(resp.read())["result"])
                conn.close()
            # load-aware routing spread the burst over both replicas —
            # which the SPREAD strategy put on different nodes
            assert len(served_nodes) >= 2, served_nodes

            # per-pool load reports reach the HEAD controller, tagged
            # with the replicas' (distinct) node ids
            ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")

            def reports():
                loads = ray_tpu.get(
                    ctrl.get_replica_loads.remote("Where"), timeout=10)
                nodes = {v.get("node") for v in loads.values()}
                return (len(loads) >= 2 and len(nodes) >= 2
                        and all(v.get("role") == "proxy-pool"
                                for v in loads.values())) and loads

            poll_until(reports, timeout=60,
                       desc="per-pool load reports from both nodes")
        finally:
            proxy.stop()
            serve.shutdown()
    finally:
        ray_tpu.shutdown()
        c.shutdown()


# ---------------------------------------------------------------------------
# elastic drain (r20): live-session migration, no re-prefill
# ---------------------------------------------------------------------------

def test_disagg_drain_migrates_live_session_token_exact(rt_serve):
    """Preemption drain: with a live decode stream in flight,
    drain_decode_replica ships the session's KV blocks to the surviving
    decode replica and the handle splices the continuation — the caller
    sees the EXACT token sequence of an undisturbed run, the prefill
    pool never re-prefills, and the drain/migration land on the event
    plane (acceptance criterion (c) of the elasticity issue)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import state

    h = serve.deploy_disagg(
        "llama-debug", name="ddrain", prefill_replicas=1,
        decode_replicas=2, max_slots=4, max_len=2048, block_size=8,
        prefill_chunk=8, seed=0)
    try:
        from conftest import poll_until

        prompt = np.random.default_rng(7).integers(0, 256, 20).tolist()
        # reference: undisturbed 40-token stream, consumed to completion
        # so its session retires (greedy sampling makes the drained
        # run's first 40 tokens comparable)
        ref = list(h.stream(prompt, 40))
        assert len(ref) == 40

        # drained run: a deliberately huge budget keeps the session
        # in flight for the whole drain dance
        g = h.stream(prompt, 1200)
        got = [next(g) for _ in range(5)]        # stream provably live

        # the live session sits on exactly one decode replica (the
        # reference session has retired): that replica is the victim
        h.decode._refresh(force=True)
        reps = list(h.decode._replicas)
        assert len(reps) == 2
        by_hex = {r._actor_id.binary().hex(): r for r in reps}

        def one_victim():
            stats = {hx: ray_tpu.get(
                r.handle_request.remote("stats", (), {}), timeout=60)
                for hx, r in by_hex.items()}
            v = [hx for hx, s in stats.items() if s["inflight"] >= 1]
            return v if len(v) == 1 else None

        victim = poll_until(one_victim, timeout=30,
                            desc="exactly one live decode session")[0]

        report = h.drain_decode_replica(victim, timeout_s=60.0)
        assert report["sessions"] == 1, report
        assert report["migrated"] == 1 and report["failed"] == 0, report

        # token-exact continuation across the splice — the destination
        # adopted the shipped KV against the fed-token transcript; any
        # re-prefill drift or handoff-token duplication breaks this
        while len(got) < 40:
            got.append(next(g))
        assert got == ref, (got, ref)
        g.close()

        # the victim exported the live session; the survivor adopted it
        vstats = ray_tpu.get(
            by_hex[victim].handle_request.remote("stats", (), {}),
            timeout=60)
        assert vstats["migrated_out"] == 1
        # no re-prefill: the prefill pool served exactly the two
        # original streams
        h.prefill._refresh(force=True)
        pstats = ray_tpu.get(
            h.prefill._replicas[0].handle_request.remote(
                "stats", (), {}), timeout=60)
        assert pstats["exported"] == 2, pstats

        # event-plane records: one drain, one migrated session bound for
        # a SURVIVING replica with real KV cargo (replica rings ship to
        # the head asynchronously: poll)
        def drain_events():
            evs = state.list_events(limit=100000)
            drains = [e for e in evs if e.get("name") == "serve_drain"]
            migs = [e for e in evs
                    if e.get("name") == "serve_session_migrated"]
            return (drains, migs) if drains and migs else None

        drains, migs = poll_until(drain_events, timeout=30,
                                  desc="drain events reach the head")
        assert int(drains[-1]["sessions"]) >= 1
        assert len(migs) == 1
        assert migs[0]["dst"] != victim
        assert int(migs[0]["kv_tokens"]) >= len(prompt)
    finally:
        h.shutdown()


def test_drain_decode_replica_argument_errors(rt_serve):
    """Victim addressing: unknown actor id is a loud error; an unknown
    node id is a no-op report (the shape a stale preemption notice
    arrives in); draining needs a surviving peer."""
    import pytest as _pytest

    from ray_tpu import serve

    h = serve.deploy_disagg(
        "llama-debug", name="ddrain2", prefill_replicas=1,
        decode_replicas=1, max_slots=2, max_len=64, block_size=8,
        prefill_chunk=8, seed=0)
    try:
        with _pytest.raises(ValueError):
            h.drain_decode_replica("feedfacefeedface")
        assert h.drain_decode_replica(node_id="no-such-node") == {
            "sessions": 0, "migrated": 0, "failed": 0, "finished": 0}
        # sole decode replica: no surviving peer to migrate to
        h.decode._refresh(force=True)
        only = h.decode._replicas[0]._actor_id.binary().hex()
        with _pytest.raises(RuntimeError):
            h.drain_decode_replica(only)
    finally:
        h.shutdown()
