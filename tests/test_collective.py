"""STORE-backend collectives between actors, XLA group on local devices."""

import numpy as np
import pytest


def test_store_collective_between_actors(rt_module):
    rt = rt_module
    from ray_tpu.collective import create_collective_group

    class Member:
        def __init__(self, rank, world):
            self.rank, self.world = rank, world

        def setup(self):
            import ray_tpu.collective as col
            col.init_collective_group(self.world, self.rank, "store", "g1")
            return True

        def do_allreduce(self):
            import ray_tpu.collective as col
            out = col.allreduce(np.full((4,), float(self.rank + 1)), "g1")
            return out

        def do_bcast_gather(self):
            import ray_tpu.collective as col
            b = col.broadcast(np.full((2,), float(self.rank)), 1, "g1")
            g = col.allgather(np.array([self.rank]), "g1")
            return b, [np.asarray(x) for x in g]

        def do_p2p(self):
            import ray_tpu.collective as col
            if self.rank == 0:
                col.send(np.array([42.0]), 1, "g1")
                return None
            if self.rank == 1:
                return col.recv(0, "g1")
            return None

    world = 3
    create_collective_group([], world, list(range(world)), "store", "g1")
    members = [rt.remote(Member).remote(r, world) for r in range(world)]
    assert all(rt.get([m.setup.remote() for m in members]))

    outs = rt.get([m.do_allreduce.remote() for m in members])
    for o in outs:
        np.testing.assert_allclose(o, np.full((4,), 6.0))

    outs = rt.get([m.do_bcast_gather.remote() for m in members])
    for b, g in outs:
        np.testing.assert_allclose(b, np.full((2,), 1.0))
        np.testing.assert_allclose(np.concatenate(g), [0, 1, 2])

    outs = rt.get([m.do_p2p.remote() for m in members])
    np.testing.assert_allclose(outs[1], [42.0])


def test_xla_group_local_devices():
    import jax
    from ray_tpu.collective.collective import XlaGroup
    from ray_tpu.collective.types import ReduceOp

    n = len(jax.local_devices())
    g = XlaGroup(n, 0, "local")
    tensors = [np.full((8, 128), float(i)) for i in range(n)]
    out = g.allreduce(tensors)
    expect = sum(range(n))
    for o in out:
        np.testing.assert_allclose(o, np.full((8, 128), float(expect)))

    gathered = g.allgather([np.full((1, 128), float(i)) for i in range(n)])
    assert np.asarray(gathered[0]).shape == (n, 128)


def test_xla_group_full_verb_matrix():
    """Verb parity with the reference device-collective surface
    (python/ray/util/collective/collective.py:311-594) on the 8-device
    CPU mesh: reduce, broadcast, permute (send/recv), alltoall."""
    import jax

    from ray_tpu.collective.collective import XlaGroup
    from ray_tpu.collective.types import ReduceOp

    n = jax.device_count()
    assert n == 8
    g = XlaGroup(n, 0, "matrix")
    tensors = [np.full((4,), float(i + 1), np.float32) for i in range(n)]

    # reduce: only the root holds the sum; others keep their input
    out = g.reduce(tensors, root_rank=2, op=ReduceOp.SUM)
    np.testing.assert_allclose(out[2], np.full((4,), sum(range(1, n + 1))))
    for i in (0, 1, 3, 7):
        np.testing.assert_allclose(out[i], tensors[i])

    # reduce with MAX
    out = g.reduce(tensors, root_rank=0, op=ReduceOp.MAX)
    np.testing.assert_allclose(out[0], np.full((4,), float(n)))

    # broadcast from root 3: everyone has root's tensor
    out = g.broadcast(tensors, root_rank=3)
    for i in range(n):
        np.testing.assert_allclose(out[i], tensors[3])

    # send/recv as ppermute: 1 -> 6, 0 -> 7; everyone else unchanged
    out = g.permute(tensors, [(1, 6), (0, 7)])
    np.testing.assert_allclose(out[6], tensors[1])
    np.testing.assert_allclose(out[7], tensors[0])
    np.testing.assert_allclose(out[0], tensors[0])
    np.testing.assert_allclose(out[5], tensors[5])

    # send() sugar
    out = g.send(tensors, dst_rank=4, src_rank=2)
    np.testing.assert_allclose(out[4], tensors[2])

    # alltoall: device i ends with everyone's chunk i
    chunk_lists = [[np.full((2,), 10 * i + j, np.float32) for j in range(n)]
                   for i in range(n)]
    out = g.alltoall(chunk_lists)
    for i in range(n):
        for j in range(n):
            np.testing.assert_allclose(out[i][j], chunk_lists[j][i])

    # existing verbs still in place
    out = g.allreduce(tensors, op=ReduceOp.MEAN)
    np.testing.assert_allclose(out[0], np.full((4,), (n + 1) / 2))


def test_xla_distributed_group_two_processes(rt_module):
    """Verb matrix across TWO actor PROCESSES x 4 virtual CPU devices each,
    in-XLA over one global jax.distributed mesh (VERDICT r3 #7 done
    criterion; reference NCCLGroup role). Rendezvous rides the named
    coordinator actor."""
    rt = rt_module
    from ray_tpu.collective import create_collective_group

    class Member:
        def __init__(self, rank, world):
            self.rank, self.world = rank, world

        def setup(self):
            import jax

            from ray_tpu.collective.collective import init_collective_group

            g = init_collective_group(self.world, self.rank,
                                      "xla_distributed", "gd1")
            return (jax.process_count(), jax.device_count(),
                    jax.local_device_count())

        def verbs(self):
            import numpy as np

            from ray_tpu.collective.collective import get_collective_group
            from ray_tpu.collective.types import ReduceOp

            g = get_collective_group("gd1")
            nloc = 4
            base = self.rank * nloc
            mine = [np.full((2,), float(base + i)) for i in range(nloc)]
            out = {}
            out["allreduce"] = g.allreduce(mine)  # sum over 8 global devs
            out["allgather"] = g.allgather(mine)
            out["bcast"] = g.broadcast(mine, root_rank=5)
            out["reduce"] = g.reduce(mine, root_rank=2)
            out["rscatter"] = g.reducescatter(
                [np.arange(8, dtype=np.float64) for _ in range(nloc)])
            chunks = [[np.full((1,), float(base + i) * 10 + j)
                       for j in range(8)] for i in range(nloc)]
            out["alltoall"] = g.alltoall(chunks)
            g.barrier()
            return out

    world = 2
    create_collective_group([], world, [0, 1], "xla_distributed", "gd1")
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    members = [
        rt.remote(Member).options(
            runtime_env={"env_vars": env}).remote(r, world)
        for r in range(world)
    ]
    infos = rt.get([m.setup.remote() for m in members], timeout=60)
    assert infos == [(2, 8, 4), (2, 8, 4)]

    outs = rt.get([m.verbs.remote() for m in members], timeout=60)
    total = sum(range(8))  # device d holds value d
    for rank, out in enumerate(outs):
        base = rank * 4
        for arr in out["allreduce"]:
            np.testing.assert_allclose(arr, np.full((2,), float(total)))
        for arr in out["allgather"]:
            np.testing.assert_allclose(
                arr, np.repeat(np.arange(8.0), 2).reshape(8, 2)
                .reshape(-1))
        for arr in out["bcast"]:
            np.testing.assert_allclose(arr, np.full((2,), 5.0))
        for i, arr in enumerate(out["reduce"]):
            want = float(total) if base + i == 2 else float(base + i)
            np.testing.assert_allclose(arr, np.full((2,), want))
        for i, arr in enumerate(out["rscatter"]):
            np.testing.assert_allclose(arr, [float(base + i) * 8])
        for i, got_chunks in enumerate(out["alltoall"]):
            want = [float(s) * 10 + (base + i) for s in range(8)]
            np.testing.assert_allclose(
                np.concatenate(got_chunks), want)
