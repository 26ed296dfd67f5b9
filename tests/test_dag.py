"""DAG API: eager execute, channels, compiled pipelines."""

import time
import uuid

import numpy as np

import pytest

import ray_tpu
from ray_tpu.dag import InputNode
from ray_tpu.experimental.channel import Channel, ChannelTimeoutError


@pytest.fixture
def rt_dag():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_channel_write_read_roundtrip():
    name = uuid.uuid4().hex[:8]
    ch = Channel(name, capacity=1 << 16, create=True)
    try:
        ch.write({"a": 1, "b": [1, 2, 3]})
        reader = Channel(name, create=False)
        assert reader.read(timeout=5) == {"a": 1, "b": [1, 2, 3]}
        # mutable: same channel carries the next value
        ch.write("second")
        assert reader.read(timeout=5) == "second"
        # no new value -> timeout
        with pytest.raises(ChannelTimeoutError):
            reader.read(timeout=0.1)
    finally:
        ch.unlink()


def test_dag_eager_execute(rt_dag):
    @ray_tpu.remote
    class Adder:
        def __init__(self, k):
            self.k = k

        def add(self, x):
            return x + self.k

    @ray_tpu.remote
    class Scaler:
        def scale(self, x):
            return x * 10

    a = Adder.remote(5)
    s = Scaler.remote()
    with InputNode() as inp:
        dag = s.scale.bind(a.add.bind(inp))
    out = ray_tpu.get(dag.execute(3))
    assert out == 80


def test_function_node_eager(rt_dag):
    @ray_tpu.remote
    def double(x):
        return 2 * x

    @ray_tpu.remote
    def inc(x):
        return x + 1

    with InputNode() as inp:
        dag = inc.bind(double.bind(inp))
    assert ray_tpu.get(dag.execute(10)) == 21


def test_compiled_dag_pipeline(rt_dag):
    @ray_tpu.remote
    class Stage:
        def __init__(self, k):
            self.k = k

        def apply(self, x):
            return x + self.k

    s1 = Stage.remote(1)
    s2 = Stage.remote(10)
    with InputNode() as inp:
        dag = s2.apply.bind(s1.apply.bind(inp))
    compiled = dag.experimental_compile()
    try:
        # repeated invocations reuse the same channels/loops
        for i in range(5):
            assert compiled.execute(i).get(timeout=30) == i + 11
    finally:
        compiled.teardown()


def test_compiled_dag_fan_in(rt_dag):
    @ray_tpu.remote
    class Worker:
        def double(self, x):
            return 2 * x

        def add(self, a, b):
            return a + b

    w1 = Worker.remote()
    w2 = Worker.remote()
    w3 = Worker.remote()
    with InputNode() as inp:
        dag = w3.add.bind(w1.double.bind(inp), w2.double.bind(inp))
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(3).get(timeout=30) == 12
        assert compiled.execute(5).get(timeout=30) == 20
    finally:
        compiled.teardown()


def test_compiled_dag_error_propagates(rt_dag):
    @ray_tpu.remote
    class Failer:
        def boom(self, x):
            if x == 13:
                raise ValueError("unlucky")
            return x

    f = Failer.remote()
    with InputNode() as inp:
        dag = f.boom.bind(inp)
    compiled = dag.experimental_compile()
    try:
        assert compiled.execute(1).get(timeout=30) == 1
        from ray_tpu.dag.compiled_dag import DAGExecutionError

        with pytest.raises(DAGExecutionError):
            compiled.execute(13).get(timeout=30)
        # pipeline survives the error
        assert compiled.execute(2).get(timeout=30) == 2
    finally:
        compiled.teardown()


def test_channel_ring_backlog_and_writer_backpressure():
    """Ring semantics: several unread values queue in slot order; a full
    ring blocks the writer (bounded -> ChannelFullError) until the
    slowest reader's cursor advances."""
    from ray_tpu.experimental.channel import Channel, ChannelFullError

    name = uuid.uuid4().hex[:8]
    ch = Channel(name, capacity=1 << 12, create=True, slots=4)
    try:
        reader = Channel(name, create=False)
        for i in range(3):
            ch.write(i)
        assert [reader.read(timeout=5) for _ in range(3)] == [0, 1, 2]
        for i in range(10):          # ring wraps across many cycles
            ch.write(("wrap", i))
            assert reader.read(timeout=5) == ("wrap", i)
        for i in range(4):           # fill every slot
            ch.write(i)
        with pytest.raises(ChannelFullError):
            ch.write(99, timeout=0.2)
        assert reader.read(timeout=5) == 0   # frees one slot
        ch.write(99, timeout=5)
        assert [reader.read(timeout=5) for _ in range(4)] == [1, 2, 3, 99]
    finally:
        ch.unlink()


def test_channel_unregistered_ring_is_bounded():
    """Before any reader registers, the ring itself bounds in-flight
    writes — a writer can never lap values a future reader is entitled
    to."""
    from ray_tpu.experimental.channel import Channel, ChannelFullError

    name = uuid.uuid4().hex[:8]
    ch = Channel(name, capacity=1 << 12, create=True, slots=3)
    try:
        for i in range(3):
            ch.write(i)
        with pytest.raises(ChannelFullError):
            ch.write(3, timeout=0.2)
        reader = Channel(name, create=False)
        assert reader.read(timeout=5) == 0   # backlog intact from value 0
    finally:
        ch.unlink()


def test_compiled_dag_pipelined_fifo_and_out_of_order_get(rt_dag):
    """max_in_flight admissions overlap; results map to THEIR invocation
    strictly FIFO even when futures are awaited out of order."""
    @ray_tpu.remote
    class Stage:
        def apply(self, x):
            return x * 10

    s = Stage.remote()
    with InputNode() as inp:
        dag = s.apply.bind(inp)
    compiled = dag.experimental_compile(max_in_flight=8)
    try:
        futs = [compiled.execute(i) for i in range(8)]
        # out-of-order: awaiting the LAST future buffers results 0..6
        # into their own futures
        assert futs[7].get(timeout=60) == 70
        assert [futs[i].get(timeout=60) for i in range(7)] == [
            i * 10 for i in range(7)]
        # a second pipelined wave reuses the same rings
        futs = [compiled.execute(i) for i in range(8)]
        assert [f.get(timeout=60) for f in futs] == [
            i * 10 for i in range(8)]
    finally:
        compiled.teardown()


def test_compiled_dag_pipeline_throughput_overlaps_stages(rt_dag):
    """A 2-stage pipeline with pipelining admits the whole wave before
    draining — all results arrive, in order."""
    @ray_tpu.remote
    class Stage:
        def __init__(self, k):
            self.k = k

        def apply(self, x):
            return x + self.k

    s1, s2 = Stage.remote(1), Stage.remote(100)
    with InputNode() as inp:
        dag = s2.apply.bind(s1.apply.bind(inp))
    compiled = dag.experimental_compile(max_in_flight=4)
    try:
        for _ in range(3):  # several waves
            futs = [compiled.execute(i) for i in range(4)]
            assert [f.get(timeout=60) for f in futs] == [
                i + 101 for i in range(4)]
    finally:
        compiled.teardown()


def test_compiled_dag_concurrent_producers_fifo(rt_dag):
    """Two threads drive the same compiled DAG: admission order pairs
    each future with ITS result (the drive lock serializes admission and
    whoever drains settles futures for everyone)."""
    import threading

    @ray_tpu.remote
    class Stage:
        def apply(self, x):
            return x + 1

    s = Stage.remote()
    with InputNode() as inp:
        dag = s.apply.bind(inp)
    compiled = dag.experimental_compile(max_in_flight=8)
    errors = []

    def drive(tid):
        try:
            for i in range(15):
                x = tid * 1000 + i
                got = compiled.execute(x).get(timeout=60)
                if got != x + 1:
                    errors.append((x, got))
        except BaseException as e:  # noqa: BLE001 — collected for assert
            errors.append(repr(e))

    try:
        threads = [threading.Thread(target=drive, args=(t,))
                   for t in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors
    finally:
        compiled.teardown()


def test_compiled_dag_error_isolation_under_pipelining(rt_dag):
    """An error in invocation k surfaces on future k only — slots k-1 and
    k+1 resolve to their own correct results."""
    @ray_tpu.remote
    class Failer:
        def boom(self, x):
            if x == 13:
                raise ValueError("unlucky")
            return x

    f = Failer.remote()
    with InputNode() as inp:
        dag = f.boom.bind(inp)
    compiled = dag.experimental_compile(max_in_flight=4)
    try:
        futs = [compiled.execute(x) for x in (1, 13, 2)]
        from ray_tpu.dag import DAGExecutionError

        assert futs[0].get(timeout=60) == 1
        with pytest.raises(DAGExecutionError):
            futs[1].get(timeout=60)
        assert futs[2].get(timeout=60) == 2
    finally:
        compiled.teardown()


def test_compiled_dag_backpressure_error(rt_dag):
    """A full pipeline (max_in_flight admissions outstanding) makes
    execute() block for a completion and raise DAGBackpressureError past
    its deadline — the shm-layer ChannelFullError never leaks."""
    @ray_tpu.remote
    class Slow:
        def apply(self, x):
            time.sleep(1.5)
            return x

    s = Slow.remote()
    with InputNode() as inp:
        dag = s.apply.bind(inp)
    compiled = dag.experimental_compile(max_in_flight=2)
    try:
        f1 = compiled.execute(1)
        f2 = compiled.execute(2)
        from ray_tpu.dag import DAGBackpressureError, DAGExecutionError

        with pytest.raises(DAGBackpressureError):
            compiled.execute(3, timeout=0.2)
        assert issubclass(DAGBackpressureError, DAGExecutionError)
        assert f1.get(timeout=60) == 1
        assert f2.get(timeout=60) == 2
        # slots freed: the same admission now succeeds
        assert compiled.execute(3, timeout=60).get(timeout=60) == 3
    finally:
        compiled.teardown()


def test_compiled_dag_execute_async(rt_dag):
    """Asyncio drivers (serve replicas) admit and await without blocking
    their loop."""
    import asyncio

    @ray_tpu.remote
    class Stage:
        def apply(self, x):
            return x * 3

    s = Stage.remote()
    with InputNode() as inp:
        dag = s.apply.bind(inp)
    compiled = dag.experimental_compile(max_in_flight=4)

    async def drive():
        futs = [await compiled.execute_async(i) for i in range(4)]
        return [await f for f in futs]

    try:
        assert asyncio.run(drive()) == [0, 3, 6, 9]
    finally:
        compiled.teardown()


def test_compiled_dag_teardown_unlinks_channels(rt_dag):
    import os

    @ray_tpu.remote
    class S:
        def f(self, x):
            return x

    s = S.remote()
    with InputNode() as inp:
        dag = s.f.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.execute(7).get(timeout=30) == 7
    paths = [ch.path for ch in compiled._channels]
    compiled.teardown()
    assert not any(os.path.exists(p) for p in paths)


def test_compiled_dag_teardown_frees_actor(rt_dag):
    @ray_tpu.remote
    class S:
        def f(self, x):
            return x

    s = S.remote()
    with InputNode() as inp:
        dag = s.f.bind(inp)
    compiled = dag.experimental_compile()
    assert compiled.execute(7).get(timeout=30) == 7
    compiled.teardown()
    # after teardown the actor serves normal calls again
    assert ray_tpu.get(s.f.remote(42), timeout=30) == 42


def test_device_channel_roundtrip_and_zero_copy(rt_dag):
    """DeviceChannel moves a jax array: raw bytes in the segment, and the
    CPU-backend reader ALIASES the channel buffer (no copy) — asserted via
    the consumer array's buffer pointer living inside the channel mapping
    (reference NCCL-channel role, torch_tensor_nccl_channel.py:29)."""
    import ctypes
    import uuid

    import jax
    import jax.numpy as jnp

    from ray_tpu.experimental.device_channel import DeviceChannel

    name = f"test-dev-{uuid.uuid4().hex[:6]}"
    ch = DeviceChannel(name, capacity=1 << 20, create=True)
    try:
        arr = jnp.arange(1024, dtype=jnp.float32) * 2.0
        ch.write(arr)
        reader = DeviceChannel(name, create=False)
        out = reader.read(timeout=5)
        assert isinstance(out, jax.Array)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(arr))
        # zero-copy assertion (CPU backend): consumer buffer lies inside
        # the reader's channel mapping
        base = ctypes.addressof(ctypes.c_char.from_buffer(reader._mm))
        ptr = out.addressable_shards[0].data.unsafe_buffer_pointer()
        assert base <= ptr < base + len(reader._mm), (
            f"consumer array not aliased into the channel segment "
            f"(ptr={ptr:#x}, seg=[{base:#x},{base + len(reader._mm):#x}))")
        # control values still travel (pickle fallback)
        ch.write({"not": "a tensor"})
        assert reader.read(timeout=5) == {"not": "a tensor"}
        del out
    finally:
        ch.unlink()


def test_compiled_dag_device_edges(rt_dag):
    """Compiled DAG with DeviceTensorType edges: jax arrays flow
    actor->actor through device channels; consumers receive jax arrays."""
    import jax

    import ray_tpu
    from ray_tpu.dag import InputNode

    @ray_tpu.remote
    class Scale:
        def apply(self, x):
            import jax
            import jax.numpy as jnp

            assert isinstance(x, jax.Array), type(x)
            return x * 2.0

    @ray_tpu.remote
    class Sum:
        def apply(self, x):
            import jax
            import jax.numpy as jnp

            assert isinstance(x, jax.Array), type(x)
            return jnp.sum(x)

    a, b = Scale.remote(), Sum.remote()
    with InputNode() as inp:
        inp.with_tensor_transport()
        mid = a.apply.bind(inp).with_tensor_transport()
        out = b.apply.bind(mid).with_tensor_transport()
    compiled = out.experimental_compile()
    try:
        import jax.numpy as jnp

        for k in range(3):
            fut = compiled.execute(jnp.ones((256,), jnp.float32) * (k + 1))
            val = fut.get(timeout=60)
            assert float(np.asarray(val)) == 2.0 * 256 * (k + 1)
    finally:
        compiled.teardown()
