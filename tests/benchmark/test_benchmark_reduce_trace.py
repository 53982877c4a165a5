"""The reduction from a trace to numbers, on hand-built traces with known
answers; and the work functions against hand counts at one shape each."""
import pytest

import bench_rehearse as br  # noqa: F401  (puts the repo on sys.path)
from benchmark import reduce_trace as rt
from benchmark.readers import (exposed_collective_share, idle_share,
                               kernel_roofline, span_host_ms, step_mfu)
from benchmark.harness import ReadContext, percentile
from benchmark.work import (flash_attention, hlo_text, rms_norm, serve_step,
                            train_step)

FUSION = "%fusion.7 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(bf16[8,128]{1,0} %p.1), kind=kLoop"
WHILE = "%while.5 = (s32[], bf16[2,8]{1,0}) while((s32[], bf16[2,8]{1,0}) %tuple.1), condition=%c, body=%b"
ALLRED = "%all-reduce.3 = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups={}"
RMS = ('%closed_call.14 = bf16[8192,4096]{1,0:T(8,128)(2,1)} custom-call('
       'bf16[8192,4096]{1,0:T(8,128)(2,1)S(1)} %bitcast.533, '
       'f32[4096]{0:T(1024)S(1)} %bitcast.584), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
FA_FWD = ('%closed_call.15 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}, '
          'f32[64,8,8,512]{3,2,1,0:T(8,128)}) custom-call(s32[1]{0:T(128)} '
          '%g.868, bf16[64,4096,128]{2,1,0:T(8,128)(2,1)} %b.553, '
          'bf16[64,4096,128]{2,1,0:T(8,128)(2,1)} %b.549, '
          'bf16[64,4096,128]{2,1,0:T(8,128)(2,1)S(1)} %b.550), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')
FA_DQ = ('%checkpoint.19 = bf16[64,4096,128]{2,1,0:T(8,128)(2,1)} '
         'custom-call(s32[1]{0:T(128)} %g, bf16[64,4096,128]{2,1,0} %a, '
         'bf16[64,4096,128]{2,1,0} %b, bf16[64,4096,128]{2,1,0} %c, '
         'bf16[64,4096,128]{2,1,0} %d, f32[64,8,4096]{2,1,0:T(8,128)S(1)} %e, '
         'f32[64,8,4096]{2,1,0:T(8,128)S(1)} %f), '
         'custom_call_target="tpu_custom_call"')
FA_DKV = FA_DQ.replace(
    "%checkpoint.19 = bf16[64,4096,128]{2,1,0:T(8,128)(2,1)} ",
    "%checkpoint.18 = (bf16[64,4096,128]{2,1,0:T(8,128)(2,1)S(1)}, "
    "bf16[64,4096,128]{2,1,0:T(8,128)(2,1)}) ")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def ctx(trace, stats=None, chips=1, model=None):
    return ReadContext(trace, rt.window_of(trace), model or {}, {}, PEAK,
                       chips, stats or {})


def test_union_clip_subtract():
    assert rt.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert rt.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert rt.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert rt.length([(0, 2), (3, 4)]) == 3


def test_names():
    assert rt.op_kind(FUSION) == "fusion" and rt.op_kind(WHILE) == "while"
    assert rt.op_kind(ALLRED) == "all-reduce" and rt.is_collective(ALLRED)
    assert rt.is_container(WHILE) and not rt.is_container(FUSION)
    assert rt.short_name(FUSION) == "%fusion fusion bf16[8,128]"
    assert rt.op_kind(RMS) == "custom-call"
    assert rt.op_kind("plain name") == "plain name"


def two_device_trace():
    """Device 0: busy [0,4) and [6,10); device 1: busy [0,2) only, then an
    all-reduce [2,5) of which [4,5) overlaps compute. Host: a step
    annotation [0,5), a read [5,7), nothing after."""
    return rt.Trace(
        device_ops={0: [(FUSION, 0, 4), (FUSION, 6, 10)],
                    1: [(FUSION, 0, 2), (ALLRED, 2, 5), (FUSION, 4, 5)]},
        annotations=[("bench.trainer_step", 0, 5), ("bench.read_loss", 5, 7),
                     ("bench.sync", 9.5, 10)])


def test_busy_idle_and_window():
    tr = two_device_trace()
    w = rt.window_of(tr)
    assert w == (0, 10)
    assert rt.length(rt.busy(tr, 0, w)) == 8
    assert rt.length(rt.busy(tr, 1, w)) == 5
    assert rt.busy_seconds(tr, w) == pytest.approx(6.5)
    assert rt.idle_share(tr, w) == pytest.approx(0.5)      # device 1
    assert idle_share.read({}, ctx(tr)) == pytest.approx(50.0)
    assert rt.window_of(tr, "bench.read_loss") == (5, 7)


def test_exposed_collective_time():
    tr = two_device_trace()
    # device 1: the all-reduce runs [2,5), compute [4,5) hides one second
    assert rt.exposed_collective_share(tr, (0, 10)) == pytest.approx(0.2)
    assert exposed_collective_share.read({}, ctx(tr)) == pytest.approx(20.0)
    one = rt.Trace(device_ops={0: tr.device_ops[0]}, annotations=[])
    assert exposed_collective_share.read({}, ctx(one)) is None


def test_gap_attribution_and_top_ops():
    tr = two_device_trace()
    b = rt.breakdown(tr, (0, 10))
    # device 1 idles most: idle [5,10) = read_loss [5,7), none [7,9.5),
    # sync [9.5,10)
    assert dict(map(tuple, b["idle_gaps"])) == pytest.approx(
        {"bench.read_loss": 2.0, "(none)": 2.5, "bench.sync": 0.5})
    ops = dict(map(tuple, b["device_ops"]))
    assert ops["%fusion fusion bf16[8,128]"] == pytest.approx((8 + 3) / 2)
    assert ops["%all-reduce all-reduce f32[1024]"] == pytest.approx(1.5)


def test_kernel_time_by_prefix_and_span_host_ms():
    tr = rt.Trace(device_ops={0: [("_fa_fwd", 0, 1), ("_fa_bwd", 1, 3),
                                  ("_rms", 3, 3.5), ("_fa_fwd", 9, 11)]},
                  annotations=[("bench.engine_step", 0, 4),
                               ("bench.engine_step", 4, 6)])
    def seconds(prefix):
        return sum(e - s for _, s, e in rt.kernel_events(
            tr, lambda n: n.startswith(prefix), (0, 10)))

    assert seconds("_fa_") == 3          # the one that passes the window is out
    assert seconds("_rms") == 0.5
    # spans: 4 s with 3.5 busy, 2 s with none -> host 0.5 and 2.0
    assert span_host_ms.read({"annotation": "bench.engine_step"},
                             ctx(tr)) == pytest.approx(1250.0)
    assert span_host_ms.read({"annotation": "bench.absent"}, ctx(tr)) is None


def test_loader_leaves_containers_out(monkeypatch):
    class E:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class L:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class P:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    planes = [P("/device:TPU:0", [
        L("XLA Modules", [E("jit_step", 0, 10_000)]),
        L("XLA Ops", [E(WHILE, 0, 9_000), E(FUSION, 1_000, 2_000)])]),
        P("/host:CPU", [L("python", [E("bench.trainer_step", 0, 500),
                                     E("$trainer.py step", 0, 400)])])]
    import jax

    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: type("D", (), {
                            "planes": planes})()))
    tr = rt.load_xplane("ignored")
    assert [n for n, _, _ in tr.device_ops[0]] == [FUSION]
    assert tr.device_ops[0][0][1:] == pytest.approx((1e-6, 3e-6))
    assert [a[0] for a in tr.annotations] == ["bench.trainer_step"]


def test_hlo_text_and_kernel_work_hand_counts():
    outs, ins = hlo_text.pallas_call(RMS)
    assert outs == [("bf16", (8192, 4096), False)]
    assert ins == [("bf16", (8192, 4096), True), ("f32", (4096,), True)]
    assert hlo_text.pallas_call(FUSION) is None
    # rms norm: x and the scale sit on chip (S(1)), only y crosses HBM
    flops, nbytes = rms_norm.work(RMS, {})
    assert flops == 4 * 8192 * 4096 and nbytes == 8192 * 4096 * 2
    assert rms_norm.work(FA_FWD, {}) is None
    # flash attention, [64, 4096, 128]: one causal matmul is
    # 64 * 4096^2 * 128 = 137.4e9 operations
    u = 64 * 4096 * 4096 * 128
    assert flash_attention.work(FA_FWD, {"causal": True})[0] == 2 * u
    assert flash_attention.work(FA_DKV, {"causal": True})[0] == 4 * u
    assert flash_attention.work(FA_DQ, {"causal": True})[0] == 3 * u
    assert flash_attention.work(FA_FWD, {"causal": False})[0] == 4 * u
    slab = 64 * 4096 * 128 * 2
    # forward: q, k in HBM, v on chip; o and the f32 statistics out
    assert flash_attention.work(FA_FWD, {})[1] == \
        4 + 3 * slab + 64 * 8 * 8 * 512 * 4
    assert flash_attention.work(RMS, {}) is None


def test_kernel_roofline_reader():
    # 2u operations at 197e12 is 1.395 ms; the event took 2.79 ms
    t = 2 * 2 * 64 * 4096 * 4096 * 128 * 0.5 / 197e12
    tr = rt.Trace(device_ops={0: [(FA_FWD, 0.0, 2 * t), (FUSION, 2 * t, 1)]},
                  annotations=[])
    got = kernel_roofline.read({"work": "flash_attention",
                                "work_params": {"causal": True}}, ctx(tr))
    assert got == pytest.approx(50.0)
    assert kernel_roofline.read({"work": "rms_norm"}, ctx(tr)) is None


def test_step_work_hand_counts_and_mfu():
    m = {"hidden_size": 4096, "intermediate_size": 14336,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "num_hidden_layers": 3, "vocab_size": 32000,
         "step_work": "train_step"}     # the configuration names its count
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    assert train_step.matmul_params(m) == 3 * layer + 4096 * 32000
    per_token = 6 * (3 * layer + 4096 * 32000) + 6 * 4096 * 4096 * 3
    assert train_step.flops(m, {"tokens": 10, "seq": 4096}) == 10 * per_token
    assert serve_step.flops(m, {"tokens": 5, "context": 100, "sampled": 2}) \
        == 2 * 3 * layer * 5 + 4 * 4096 * 3 * 100 + 2 * 4096 * 32000 * 2
    tr = rt.Trace(device_ops={0: [(FUSION, 0, 1)]},
                  annotations=[("bench.engine_step", 0, 0.5),
                               ("bench.sync", 0.5, 2.0)])
    stats = {"traced_work": {"tokens": 8192, "seq": 4096}}
    want = 100 * 8192 * per_token / (2.0 * 197e12)
    assert step_mfu.read({"time": "window"},
                         ctx(tr, stats, model=m)) == pytest.approx(want)
    assert step_mfu.read({"time": "bench.engine_step"},
                         ctx(tr, stats, model=m)) == pytest.approx(4 * want)
    assert step_mfu.read({"time": "window"},
                         ctx(tr, {}, model=m)) is None


def test_percentile():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 95) == 95
    assert percentile([10, 20], 95) == pytest.approx(19.5)
