"""Tests for the native C++ runtime (csrc/ via ctypes).

Covers: flags registry, profiler spans + chrome trace, stat monitor, arena
allocator, blocking queue, parallel collate, and the graph IR (build, topo,
DCE, serialize round-trip) — the native analogs of SURVEY.md §2.1/§2.3.
"""
import ctypes
import json
import threading

import numpy as np
import pytest

from paddle_tpu.core import native

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


@pytest.fixture(scope="module")
def lib():
    return native.load()


class TestFlags:
    def test_define_set_get(self, lib):
        assert lib.pt_flag_define(b"test_flag_i", 1, b"42", b"help") == 0
        assert lib.pt_flag_get(b"test_flag_i") == b"42"
        assert lib.pt_flag_set(b"test_flag_i", b"7") == 0
        assert lib.pt_flag_get(b"test_flag_i") == b"7"
        assert lib.pt_flag_type(b"test_flag_i") == 1

    def test_unknown_flag_errors(self, lib):
        assert lib.pt_flag_set(b"no_such_flag_xyz", b"1") == -1
        assert b"unknown flag" in lib.pt_last_error()

    def test_python_set_get_flags(self):
        import paddle_tpu as paddle
        paddle.set_flags({"FLAGS_check_nan_inf": False})
        out = paddle.get_flags(["FLAGS_check_nan_inf"])
        assert out["FLAGS_check_nan_inf"] is False


class TestStats:
    def test_stats(self, lib):
        lib.pt_stat_add(b"STAT_test", 5)
        lib.pt_stat_add(b"STAT_test", 7)
        assert lib.pt_stat_get(b"STAT_test") == 12


class TestArena:
    def test_alloc_free_coalesce(self, lib):
        a = lib.pt_arena_create(1 << 20)
        ptrs = [lib.pt_arena_alloc(a, 1000) for _ in range(10)]
        assert all(p is not None for p in ptrs)
        assert len(set(ptrs)) == 10
        in_use = ctypes.c_int64()
        peak = ctypes.c_int64()
        res = ctypes.c_int64()
        lib.pt_arena_stats(a, ctypes.byref(in_use), ctypes.byref(peak),
                           ctypes.byref(res))
        assert in_use.value >= 10 * 1000
        for p in ptrs:
            assert lib.pt_arena_free(a, p) == 0
        lib.pt_arena_stats(a, ctypes.byref(in_use), ctypes.byref(peak),
                           ctypes.byref(res))
        assert in_use.value == 0
        # after full free + coalescing, a big block must fit w/o growth
        before = res.value
        big = lib.pt_arena_alloc(a, (1 << 20) - 4096)
        assert big is not None
        lib.pt_arena_stats(a, ctypes.byref(in_use), ctypes.byref(peak),
                           ctypes.byref(res))
        assert res.value == before
        lib.pt_arena_destroy(a)

    def test_double_free_errors(self, lib):
        a = lib.pt_arena_create(1 << 16)
        p = lib.pt_arena_alloc(a, 64)
        assert lib.pt_arena_free(a, p) == 0
        assert lib.pt_arena_free(a, p) == -1
        lib.pt_arena_destroy(a)


class TestQueue:
    def test_push_pop_fifo(self, lib):
        q = lib.pt_queue_create(4)
        for i in range(4):
            assert lib.pt_queue_push(q, i + 1, i * 10, i, 100) == 0
        data = ctypes.c_void_p()
        a = ctypes.c_int64()
        b = ctypes.c_int64()
        for i in range(4):
            assert lib.pt_queue_pop(q, ctypes.byref(data), ctypes.byref(a),
                                    ctypes.byref(b), 100) == 0
            assert data.value == i + 1
            assert a.value == i * 10
        lib.pt_queue_destroy(q)

    def test_timeout_and_close(self, lib):
        q = lib.pt_queue_create(1)
        data = ctypes.c_void_p()
        a = ctypes.c_int64()
        b = ctypes.c_int64()
        # empty pop times out
        assert lib.pt_queue_pop(q, ctypes.byref(data), ctypes.byref(a),
                                ctypes.byref(b), 50) == 1
        # full push times out
        assert lib.pt_queue_push(q, 1, 0, 0, 50) == 0
        assert lib.pt_queue_push(q, 2, 0, 0, 50) == 1
        lib.pt_queue_close(q)
        assert lib.pt_queue_push(q, 3, 0, 0, 50) == 2
        # drain then closed
        assert lib.pt_queue_pop(q, ctypes.byref(data), ctypes.byref(a),
                                ctypes.byref(b), 50) == 0
        assert lib.pt_queue_pop(q, ctypes.byref(data), ctypes.byref(a),
                                ctypes.byref(b), 50) == 2
        lib.pt_queue_destroy(q)

    def test_blocking_producer_consumer(self, lib):
        q = lib.pt_queue_create(2)
        got = []

        def consumer():
            data = ctypes.c_void_p()
            a = ctypes.c_int64()
            b = ctypes.c_int64()
            while True:
                rc = lib.pt_queue_pop(q, ctypes.byref(data), ctypes.byref(a),
                                      ctypes.byref(b), 5000)
                if rc != 0:
                    break
                got.append(a.value)

        t = threading.Thread(target=consumer)
        t.start()
        for i in range(20):
            assert lib.pt_queue_push(q, 1, i, 0, 5000) == 0
        lib.pt_queue_close(q)
        t.join(10)
        assert got == list(range(20))
        lib.pt_queue_destroy(q)


class TestCollate:
    def test_stack_matches_numpy(self, lib):
        rng = np.random.RandomState(0)
        samples = [np.ascontiguousarray(rng.randn(16, 33).astype("float32"))
                   for _ in range(32)]
        item_bytes = samples[0].nbytes
        dst = np.empty((32, 16, 33), dtype="float32")
        srcs = (ctypes.c_void_p * 32)(
            *[s.ctypes.data_as(ctypes.c_void_p).value for s in samples])
        rc = lib.pt_collate_stack(dst.ctypes.data_as(ctypes.c_void_p), srcs,
                                  32, item_bytes)
        assert rc == 0
        np.testing.assert_array_equal(dst, np.stack(samples))

    def test_large_parallel_path(self, lib):
        rng = np.random.RandomState(1)
        n = 64
        samples = [np.ascontiguousarray(rng.randn(256, 256).astype("float32"))
                   for _ in range(n)]
        dst = np.empty((n, 256, 256), dtype="float32")
        srcs = (ctypes.c_void_p * n)(
            *[s.ctypes.data_as(ctypes.c_void_p).value for s in samples])
        assert lib.pt_collate_stack(dst.ctypes.data_as(ctypes.c_void_p), srcs,
                                    n, samples[0].nbytes) == 0
        np.testing.assert_array_equal(dst, np.stack(samples))


class TestGraphIR:
    def _tiny_prog(self, lib):
        p = lib.pt_prog_create()
        shape = (ctypes.c_int64 * 2)(2, 3)
        lib.pt_block_add_var(p, 0, b"x", 5, shape, 2, 0)
        lib.pt_block_add_var(p, 0, b"w", 5, shape, 2, 1)
        lib.pt_block_add_var(p, 0, b"y", 5, shape, 2, 0)
        op = lib.pt_block_add_op(p, 0, b"matmul_v2")
        lib.pt_op_add_input(p, 0, op, b"X", b"x")
        lib.pt_op_add_input(p, 0, op, b"Y", b"w")
        lib.pt_op_add_output(p, 0, op, b"Out", b"y")
        lib.pt_op_set_attr_bool(p, 0, op, b"trans_x", 0)
        lib.pt_op_set_attr_float(p, 0, op, b"alpha", 1.5)
        lib.pt_op_set_attr_ints(p, 0, op, b"axes",
                                (ctypes.c_int64 * 2)(0, 1), 2)
        return p

    def test_build_and_json(self, lib):
        p = self._tiny_prog(lib)
        n = lib.pt_prog_to_json(p, None, 0)
        buf = ctypes.create_string_buffer(n)
        lib.pt_prog_to_json(p, buf, n)
        prog = json.loads(buf.value.decode())
        blk = prog["blocks"][0]
        assert [v["name"] for v in blk["vars"]] == ["x", "w", "y"]
        op = blk["ops"][0]
        assert op["type"] == "matmul_v2"
        assert op["inputs"]["X"] == ["x"]
        assert op["attrs"]["alpha"] == 1.5
        assert op["attrs"]["axes"] == [0, 1]
        lib.pt_prog_destroy(p)

    def test_serialize_roundtrip(self, lib):
        p = self._tiny_prog(lib)
        n = lib.pt_prog_serialize(p, None, 0)
        buf = ctypes.create_string_buffer(n)
        assert lib.pt_prog_serialize(p, buf, n) == n
        p2 = lib.pt_prog_deserialize(buf.raw, n)
        assert p2 is not None
        n2 = lib.pt_prog_to_json(p2, None, 0)
        jb = ctypes.create_string_buffer(n2)
        lib.pt_prog_to_json(p2, jb, n2)
        n1 = lib.pt_prog_to_json(p, None, 0)
        jb1 = ctypes.create_string_buffer(n1)
        lib.pt_prog_to_json(p, jb1, n1)
        assert jb.value == jb1.value
        lib.pt_prog_destroy(p)
        lib.pt_prog_destroy(p2)

    def test_topo_order_reorders(self, lib):
        # program written out of order: c = a+b declared after d = c*c
        p = lib.pt_prog_create()
        shape = (ctypes.c_int64 * 1)(4)
        for name in (b"a", b"b", b"c", b"d"):
            lib.pt_block_add_var(p, 0, name, 5, shape, 1, 0)
        mul = lib.pt_block_add_op(p, 0, b"elementwise_mul")
        lib.pt_op_add_input(p, 0, mul, b"X", b"c")
        lib.pt_op_add_input(p, 0, mul, b"Y", b"c")
        lib.pt_op_add_output(p, 0, mul, b"Out", b"d")
        add = lib.pt_block_add_op(p, 0, b"elementwise_add")
        lib.pt_op_add_input(p, 0, add, b"X", b"a")
        lib.pt_op_add_input(p, 0, add, b"Y", b"b")
        lib.pt_op_add_output(p, 0, add, b"Out", b"c")
        out = (ctypes.c_int32 * 2)()
        # last-writer-before semantics: op0 (mul) reads c which is only
        # produced later (op1) — no backward dep is created, both roots.
        assert lib.pt_block_topo_order(p, 0, out) == 2
        lib.pt_prog_destroy(p)

    def test_topo_dependency_chain(self, lib):
        p = lib.pt_prog_create()
        shape = (ctypes.c_int64 * 1)(4)
        for name in (b"a", b"b", b"c"):
            lib.pt_block_add_var(p, 0, name, 5, shape, 1, 0)
        op1 = lib.pt_block_add_op(p, 0, b"relu")
        lib.pt_op_add_input(p, 0, op1, b"X", b"a")
        lib.pt_op_add_output(p, 0, op1, b"Out", b"b")
        op2 = lib.pt_block_add_op(p, 0, b"relu")
        lib.pt_op_add_input(p, 0, op2, b"X", b"b")
        lib.pt_op_add_output(p, 0, op2, b"Out", b"c")
        out = (ctypes.c_int32 * 2)()
        assert lib.pt_block_topo_order(p, 0, out) == 2
        assert list(out) == [0, 1]
        lib.pt_prog_destroy(p)

    def test_dce_prunes_dead_ops(self, lib):
        p = lib.pt_prog_create()
        shape = (ctypes.c_int64 * 1)(4)
        for name in (b"a", b"live", b"dead"):
            lib.pt_block_add_var(p, 0, name, 5, shape, 1, 0)
        live_op = lib.pt_block_add_op(p, 0, b"relu")
        lib.pt_op_add_input(p, 0, live_op, b"X", b"a")
        lib.pt_op_add_output(p, 0, live_op, b"Out", b"live")
        dead_op = lib.pt_block_add_op(p, 0, b"sigmoid")
        lib.pt_op_add_input(p, 0, dead_op, b"X", b"a")
        lib.pt_op_add_output(p, 0, dead_op, b"Out", b"dead")
        removed = lib.pt_prog_dce(p, 0, b"live")
        assert removed == 1
        assert lib.pt_block_num_ops(p, 0) == 1
        lib.pt_prog_destroy(p)


class TestNativeExecutor:
    """csrc/executor.cc: dep-counted parallel DAG executor + wave schedule
    (ParallelExecutor/details SSA-graph executor parity)."""

    def _diamond_prog(self, lib):
        import ctypes
        from paddle_tpu.core import native
        prog = lib.pt_prog_create()
        shp = (ctypes.c_int64 * 1)(1)
        for name in (b"a", b"b", b"c", b"d"):
            native.check(lib.pt_block_add_var(prog, 0, name, 0, shp, 1, 0),
                         lib)
        # op0: a->b ; op1: a->c ; op2: (b,c)->d   (diamond)
        specs = [(b"src0", [b"a"], [b"b"]), (b"src1", [b"a"], [b"c"]),
                 (b"join", [b"b", b"c"], [b"d"])]
        for typ, ins, outs in specs:
            op = native.check(lib.pt_block_add_op(prog, 0, typ), lib)
            for i, v in enumerate(ins):
                native.check(lib.pt_op_add_input(prog, 0, op, b"X%d" % i, v),
                             lib)
            for i, v in enumerate(outs):
                native.check(lib.pt_op_add_output(prog, 0, op, b"O%d" % i, v),
                             lib)
        return prog

    def test_levels_diamond(self, lib):
        import ctypes
        from paddle_tpu.core import native
        prog = self._diamond_prog(lib)
        try:
            buf = (ctypes.c_int32 * 3)()
            n = native.check(lib.pt_exec_levels(prog, 0, buf, 3), lib)
            assert n == 3
            assert list(buf) == [0, 0, 1]  # two sources parallel, join after
        finally:
            lib.pt_prog_destroy(prog)

    def test_run_respects_dependencies(self, lib):
        from paddle_tpu.core import native
        prog = self._diamond_prog(lib)
        exec_ = lib.pt_exec_create(4)
        order = []

        def cb(op_idx, _ud):
            order.append(int(op_idx))

        cfn = native.EXEC_CALLBACK(cb)
        try:
            native.check(lib.pt_exec_run(exec_, prog, 0, cfn, None), lib)
        finally:
            lib.pt_exec_destroy(exec_)
            lib.pt_prog_destroy(prog)
        assert sorted(order) == [0, 1, 2]
        assert order.index(2) == 2  # join ran last

    def test_program_parallel_schedule_api(self):
        import paddle_tpu as paddle
        import numpy as np
        paddle.enable_static()
        try:
            import paddle_tpu.static as static
            main = static.Program()
            start = static.Program()
            with static.program_guard(main, start):
                x = static.data("x", [2, 4], "float32")
                a = x * 2.0
                b = x + 1.0
                c = a + b
            levels = main.parallel_schedule()
            assert len(levels) >= 3
            assert max(levels) >= 1
        finally:
            paddle.disable_static()

    def test_run_host_parallel_executes_all(self):
        import paddle_tpu as paddle
        paddle.enable_static()
        try:
            import paddle_tpu.static as static
            main = static.Program()
            start = static.Program()
            with static.program_guard(main, start):
                x = static.data("x", [2], "float32")
                y = x * 2.0 + 1.0
            seen = []
            main.run_host_parallel(lambda i: seen.append(i), num_threads=2)
            assert sorted(seen) == list(range(len(main.global_block().ops))) \
                or len(seen) >= 2
        finally:
            paddle.disable_static()
