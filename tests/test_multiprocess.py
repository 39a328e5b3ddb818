"""Real multi-process distributed tests (slow lane).

Spawns 2-3 python processes joined into one jax.distributed CPU cluster
(coordination service over TCP — the DCN regime) and exercises the EAGER
cross-process paths of paddle_tpu.distributed: whole-world collectives vs
numpy oracles, p2p send/recv round-trips, rank-subgroup collectives over
the wire channel, and a data-parallel loss-parity run.

Reference pattern: tests/unittests/test_collective_base.py:32 (subprocess
cluster, per-rank result files, oracle asserts) and test_dist_base.py:778
(loss parity, not throughput).
"""
import json
import os
import socket
import subprocess
import sys
import tempfile

import numpy as np
import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports.append(s.getsockname()[1])
        socks.append(s)
    for s in socks:
        s.close()
    return ports


WORKER = r"""
import json, os, sys
import numpy as np
import jax
jax.distributed.initialize(
    coordinator_address=os.environ["COORD"],
    num_processes=int(os.environ["WORLD"]),
    process_id=int(os.environ["RANK"]))
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

rank = jax.process_index()
world = jax.process_count()
res = {}

def run_collectives():
    # all_reduce sum/max
    t = paddle.to_tensor(np.arange(6, dtype="float32").reshape(2, 3)
                         * (rank + 1))
    dist.all_reduce(t)
    res["all_reduce_sum"] = t.numpy().tolist()
    t2 = paddle.to_tensor(np.full((4,), float(rank), "float32"))
    dist.all_reduce(t2, op=dist.ReduceOp.MAX)
    res["all_reduce_max"] = t2.numpy().tolist()
    # broadcast: genuinely divergent host state
    tb = paddle.to_tensor(np.full((3,), float(rank * 10 + 7), "float32"))
    dist.broadcast(tb, src=1)
    res["broadcast"] = tb.numpy().tolist()
    # all_gather
    lst = []
    dist.all_gather(lst, paddle.to_tensor(
        np.full((2,), float(rank), "float32")))
    res["all_gather"] = [x.numpy().tolist() for x in lst]
    # reduce_scatter
    trs = paddle.to_tensor(
        (np.arange(2 * world, dtype="float32") + rank))
    dist.reduce_scatter(trs)
    res["reduce_scatter"] = trs.numpy().tolist()
    # alltoall: chunk i of rank j -> rank i
    ta = paddle.to_tensor(
        np.asarray([[rank * 10 + i] for i in range(world)], "float32"))
    out = dist.alltoall(ta)
    res["alltoall"] = np.asarray(out.numpy()).reshape(-1).tolist()
    dist.barrier()
    res["barrier"] = True
    # p2p ring: rank r sends to (r+1) % world, receives from (r-1) % world
    send_val = np.full((2, 2), float(rank + 1), "float32")
    dist.send(paddle.to_tensor(send_val), dst=(rank + 1) % world)
    tr = paddle.to_tensor(np.zeros((2, 2), "float32"))
    dist.recv(tr, src=(rank - 1) % world)
    res["recv_ring"] = tr.numpy().tolist()

def run_subgroup():
    # proper subset {0, last}: members exchange over the wire channel,
    # the middle rank must pass through untouched
    ranks = [0, world - 1]
    g = dist.new_group(ranks=ranks)
    t = paddle.to_tensor(np.full((2,), float(rank + 1), "float32"))
    dist.all_reduce(t, group=g)
    res["sub_all_reduce"] = t.numpy().tolist()
    tb = paddle.to_tensor(np.full((2,), float(rank * 100), "float32"))
    dist.broadcast(tb, src=world - 1, group=g)
    res["sub_broadcast"] = tb.numpy().tolist()
    lst = []
    dist.all_gather(lst, paddle.to_tensor(
        np.full((1,), float(rank), "float32")), group=g)
    res["sub_all_gather"] = [x.numpy().tolist() for x in lst]
    dist.barrier(group=g)
    res["sub_barrier"] = True

def run_dp_parity():
    # data-parallel SGD with eager grad all_reduce == serial full batch
    import paddle_tpu.nn.functional as F
    from paddle_tpu import nn
    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype("float32")
    Y = rng.randint(0, 3, (8,)).astype("int64")

    def make():
        paddle.seed(0)
        m = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 3))
        o = paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=m.parameters())
        return m, o

    # distributed: this rank's shard
    m, o = make()
    shard = slice(rank * (8 // world), (rank + 1) * (8 // world))
    dp_losses = []
    for _ in range(4):
        loss = F.cross_entropy(m(paddle.to_tensor(X[shard])),
                               paddle.to_tensor(Y[shard]))
        loss.backward()
        for p in m.parameters():
            if p.grad is not None:
                dist.all_reduce(p.grad, op=dist.ReduceOp.AVG)
        o.step()
        o.clear_grad()
        ls = loss.clone()
        dist.all_reduce(ls, op=dist.ReduceOp.AVG)
        dp_losses.append(float(ls.numpy()))
    res["dp_losses"] = dp_losses

    # serial oracle on the full batch (every rank computes it; identical)
    m2, o2 = make()
    serial = []
    for _ in range(4):
        loss = F.cross_entropy(m2(paddle.to_tensor(X[:world * (8 // world)])),
                               paddle.to_tensor(Y[:world * (8 // world)]))
        loss.backward()
        o2.step()
        o2.clear_grad()
        serial.append(float(loss.numpy()))
    res["serial_losses"] = serial

mode = os.environ["MODE"]
if mode == "collectives":
    run_collectives()
elif mode == "subgroup":
    run_subgroup()
elif mode == "dp":
    run_dp_parity()
with open(os.environ["OUT"], "w") as f:
    json.dump(res, f)
"""


def _spawn(world, mode):
    ports = _free_ports(1 + world)
    coord = f"127.0.0.1:{ports[0]}"
    outs = []
    procs = []
    tmp = tempfile.mkdtemp(prefix="pt_mp_")
    for r in range(world):
        out = os.path.join(tmp, f"r{r}.json")
        outs.append(out)
        env = dict(os.environ)
        env.update({
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "COORD": coord, "WORLD": str(world), "RANK": str(r),
            "MODE": mode, "OUT": out,
            "PADDLE_TPU_P2P_BASE_PORT": str(ports[1]),
            "PADDLE_TPU_P2P_ENDPOINTS": ",".join(
                f"127.0.0.1:{p}" for p in ports[1:1 + world]),
            "PADDLE_TPU_P2P_RECV_TIMEOUT": "120",
            # every frame HMAC-authenticated end-to-end (wire.py)
            "PADDLE_TPU_WIRE_SECRET": "mp-test-secret",
        })
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    results = []
    errs = []
    for r, p in enumerate(procs):
        try:
            _, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise AssertionError(f"rank {r} timed out; stderr unknown")
        errs.append(err.decode(errors="replace")[-2500:])
        if p.returncode != 0:
            raise AssertionError(
                f"rank {r} exited {p.returncode}:\n{errs[-1]}")
        with open(outs[r]) as f:
            results.append(json.load(f))
    return results


class TestTwoProcessCollectives:
    def test_whole_world_collectives_and_p2p(self):
        world = 2
        res = _spawn(world, "collectives")
        base = np.arange(6, dtype="float32").reshape(2, 3)
        want_sum = sum(base * (r + 1) for r in range(world))
        for r in range(world):
            np.testing.assert_allclose(res[r]["all_reduce_sum"], want_sum)
            np.testing.assert_allclose(res[r]["all_reduce_max"],
                                       [world - 1.0] * 4)
            # broadcast src=1
            np.testing.assert_allclose(res[r]["broadcast"], [17.0] * 3)
            np.testing.assert_allclose(
                res[r]["all_gather"],
                [[float(i)] * 2 for i in range(world)])
            # reduce_scatter: sum_j (arange(2*world)+j) chunked
            full = sum(np.arange(2 * world, dtype="float32") + j
                       for j in range(world))
            np.testing.assert_allclose(res[r]["reduce_scatter"],
                                       full[r * 2:(r + 1) * 2])
            # alltoall: rank r receives chunk r of every rank j = j*10+r
            np.testing.assert_allclose(
                res[r]["alltoall"], [j * 10.0 + r for j in range(world)])
            assert res[r]["barrier"] is True
            # ring recv: value from (r-1) % world is (r-1)%world + 1
            prev = (r - 1) % world
            np.testing.assert_allclose(res[r]["recv_ring"],
                                       np.full((2, 2), prev + 1.0))


class TestThreeProcessSubgroup:
    def test_subgroup_collectives_skip_nonmembers(self):
        world = 3
        res = _spawn(world, "subgroup")
        # members are ranks 0 and 2; rank 1 must be untouched
        np.testing.assert_allclose(res[0]["sub_all_reduce"], [4.0, 4.0])
        np.testing.assert_allclose(res[2]["sub_all_reduce"], [4.0, 4.0])
        np.testing.assert_allclose(res[1]["sub_all_reduce"], [2.0, 2.0])
        np.testing.assert_allclose(res[0]["sub_broadcast"], [200.0, 200.0])
        np.testing.assert_allclose(res[2]["sub_broadcast"], [200.0, 200.0])
        np.testing.assert_allclose(res[1]["sub_broadcast"], [100.0, 100.0])
        for r in (0, 2):
            np.testing.assert_allclose(res[r]["sub_all_gather"],
                                       [[0.0], [2.0]])
        assert res[1]["sub_all_gather"] == []
        assert all(res[r]["sub_barrier"] for r in range(world))


class TestDataParallelLossParity:
    def test_dp_matches_serial(self):
        world = 2
        res = _spawn(world, "dp")
        for r in range(world):
            np.testing.assert_allclose(res[r]["dp_losses"],
                                       res[r]["serial_losses"],
                                       rtol=1e-5, atol=1e-6)
        # both ranks agree on the averaged loss stream
        np.testing.assert_allclose(res[0]["dp_losses"], res[1]["dp_losses"],
                                   rtol=1e-6, atol=1e-7)
