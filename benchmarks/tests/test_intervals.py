"""Union / exposed arithmetic pinned at its edges on synthetic intervals."""

from harness import intervals as iv
from harness import xplane


def test_union_touching_nested_empty():
    assert iv.union([]) == []
    assert iv.union([(3, 3), (5, 4)]) == []                 # empty, reversed
    assert iv.union([(0, 1), (1, 2)]) == [(0, 2)]           # touching
    assert iv.union([(0, 10), (2, 3), (4, 5)]) == [(0, 10)]  # nested
    assert iv.union([(5, 6), (0, 1), (0.5, 2)]) == [(0, 2), (5, 6)]
    assert iv.total([(0, 10), (2, 3), (9, 12)]) == 12


def test_subtract_and_gaps():
    assert iv.subtract([(0, 10)], []) == [(0, 10)]
    assert iv.subtract([], [(0, 10)]) == []
    assert iv.subtract([(0, 10)], [(0, 10)]) == []
    assert iv.subtract([(0, 10)], [(2, 3), (3, 5), (9, 12)]) == \
        [(0, 2), (5, 9)]
    assert iv.subtract([(0, 4), (6, 10)], [(3, 7)]) == [(0, 3), (7, 10)]
    assert iv.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert iv.gaps([(1, 2), (2, 3), (5, 6)], 0, 10) == \
        [(0, 1), (3, 5), (6, 10)]
    assert iv.gaps([], 0, 1) == [(0, 1)]


def _trace(ops, modules=(), host=()):
    t = xplane.Trace()
    t.ops[0] = list(ops)
    t.modules[0] = list(modules)
    t.host = list(host)
    return t


def test_collectives_sync_async_and_exposed():
    ops = [("fusion.1", 0, 40), ("all-reduce-start.1", 30, 31),
           ("fusion.2", 45, 60), ("all-reduce-done.1", 60, 80),
           ("all-gather.3", 90, 100), ("copy.4", 95, 100)]
    # async all-reduce in flight 30..80, sync all-gather 90..100
    assert xplane.collective_intervals(ops) == [(30, 80), (90, 100)]
    t = _trace(ops, modules=[("jit_step", 0, 100)],
               host=[("bench.traced_window", 0, 100)])
    r = xplane.reduce(t, steps=2)
    assert r["window_s"] == 100e-9
    # busy = an op runs or a collective is in flight: 0..80, 90..100
    assert r["busy_s"] == 90e-9
    assert r["step_device_s"] == 50e-9
    assert r["collective_s"] == 30e-9                 # (50 + 10) / 2 steps
    # exposed: 30..80 minus fusions (..40, 45..60) = 40..45, 60..80 = 25;
    # 90..100 minus copy 95..100 = 5 -> 30 over 2 steps
    assert r["collective_exposed_s"] == 15e-9
    # the same all-reduce as the profiler's async line shows it: one
    # event from start to done; the halves in XLA Ops are then ignored
    t.async_ops[0] = [("all-reduce-start.1", 30, 80), ("copy-start.9", 0, 99)]
    assert xplane.reduce(t, steps=2) == r


def test_a_collective_is_known_by_its_opcode_not_its_name():
    text = ("%psum.3164 = f32[1024,30528]{0,1:T(8,128)} all-reduce("
            "f32[1024,30528]{0,1:T(8,128)} %bitcast_convert_fusion), "
            "channel_id=1, replica_groups={{0,1,2,3}}")
    assert xplane.instruction_name(text) == "psum.3164"
    assert xplane.opcode(text) == "all-reduce"
    assert xplane.opcode("%all-reduce.3 = (f32[8]{0:T(8)}, f32[8]{0}) "
                         "all-reduce(f32[8]{0} %a, f32[8]{0} %b)") == \
        "all-reduce"
    assert xplane.opcode("%fusion.24 = (f32[8]{0:T(8,128)S(1)}) fusion("
                         "f32[8]{0} %p), kind=kLoop") == "fusion"
    assert xplane.opcode("%psum.3164 = all-reduce(") == "all-reduce"
    assert xplane.opcode("all-gather.3") == "all-gather"      # bare name
    assert xplane.opcode("copy-start.854") == "copy-start"
    ops = [("psum.3164", 0, 10), ("fusion.1", 10, 20)]
    assert xplane.collective_intervals(ops) == []
    assert xplane.collective_intervals(
        ops, opcodes={"psum.3164": "all-reduce"}) == [(0, 10)]
    t = _trace(ops, modules=[("jit_step", 0, 20)],
               host=[("bench.traced_window", 0, 20)])
    t.opcodes["psum.3164"] = "all-reduce"
    r = xplane.reduce(t, steps=1)
    assert r["collective_s"] == 10e-9 and r["collective_exposed_s"] == 10e-9


def test_no_collectives_reads_zero_and_window_clips():
    t = _trace([("fusion.1", -10, 10), ("fusion.2", 90, 120)],
               modules=[("jit_step", -10, 120)],
               host=[("bench.traced_window", 0, 100),
                     ("bench.fused_step", 10, 30), ("bench.block", 30, 90)])
    r = xplane.reduce(t, steps=1)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
    assert r["busy_s"] == 20e-9 and r["step_device_s"] == 100e-9
    assert xplane.idle_gaps(t) == [["bench.block", 80e-9]]
    assert xplane.top_device_ops(t) == [["fusion.1", 10e-9],
                                        ["fusion.2", 10e-9]]
    assert xplane.op_seconds(t, ["fusion.2"], steps=1) == 10e-9
    assert xplane.instruction_name(
        "%fusion.24 = (f32[8]{0}) fusion(f32[8]{0} %p.1), kind=kLoop") == \
        "fusion.24"


def test_mosaic_ops_from_hlo_text():
    text = (
        '  %attn.72 = (bf16[1,2]{1,0}) custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/jvp(GPT)/h0/attn/pallas_call" stack_frame_id=2}, '
        'backend_config={"x":"y"}\n'
        '  %custom-call.5 = s32[8]{0} custom-call(%c), '
        'custom_call_target="Sharding", metadata={op_name="jit(step)/x"}\n'
        '  ROOT %attn.9 = bf16[1]{0} custom-call(%d), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/transpose(jvp(GPT))/h0/attn/pallas_call"}\n')
    assert xplane.mosaic_ops(text) == {
        "attn.72": "jit(step)/jvp(GPT)/h0/attn/pallas_call",
        "attn.9": "jit(step)/transpose(jvp(GPT))/h0/attn/pallas_call"}
