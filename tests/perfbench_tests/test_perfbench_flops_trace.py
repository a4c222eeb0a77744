"""perfbench/flops.py against hand-worked cases, and perfbench/
trace_reduce.py against a hand-built trace, a trace the CPU profiler
writes here, and a small trace recorded on the chip."""

import glob
import os

import pytest

from perfbench import flops, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = {"n_layer": 1, "n_head": 2, "d_key": 4, "d_value": 4, "d_model": 8,
        "d_inner_hid": 16, "trg_vocab_size": 10}


def test_forward_flops_by_hand():
    # batch 1, source 3, target 2; hk = hv = 8
    proj, ffn = 2 * 8 * 32, 2 * 2 * 8 * 16           # per token
    enc = 3 * (proj + ffn) + (2 * 3 * 3 * 8) * 2
    dec = (2 * (proj + ffn) + 2 * 2 * 8 * 16 + 3 * 2 * 8 * 16
           + (2 * 2 * 2 * 8) * 2 * 0.5          # causal self
           + (2 * 2 * 3 * 8) * 2)               # cross
    head = 2 * 2 * 8 * 10
    assert flops.forward_flops(TINY, 1, 3, 2) == enc + dec + head
    assert flops.train_step_flops(TINY, 1, 3, 2) == 3 * (enc + dec + head)
    assert flops.forward_flops(TINY, 5, 3, 2) == 5 * (enc + dec + head)


def test_big_step_is_what_the_issue_reckoned():
    big = {"n_layer": 6, "n_head": 16, "d_key": 64, "d_value": 64,
           "d_model": 1024, "d_inner_hid": 4096, "trg_vocab_size": 32768}
    assert flops.matmul_params(big) == 209715200
    per_pair = 6 * flops.matmul_params(big)     # 6 x params per token pair
    got = flops.train_step_flops(big, 64, 256, 256)
    assert 64 * 256 * per_pair < got < 64 * 256 * per_pair * 1.06
    assert got == pytest.approx(2.14e13, rel=0.01)


def test_flash_kernel_calls_by_hand():
    ops, bytes_ = flops.flash_kernel_call("fwd", 2 * 4, 8, 16, 64, 2, False)
    assert ops == 2 * (2 * 8 * 16 * 256 * 2)
    assert bytes_ == 2 * 4 * 64 * (2 * 8 + 2 * 16) * 2 + 2 * 4 * 8 * 4
    half, _ = flops.flash_kernel_call("fwd", 8, 8, 8, 64, 2, causal=True)
    full, _ = flops.flash_kernel_call("fwd", 8, 8, 8, 64, 2, causal=False)
    assert half == full / 2
    # backward kernels form the scores again: 3 and 4 products for 2
    dq, dq_bytes = flops.flash_kernel_call("dq", 8, 8, 16, 64, 2, False)
    dkv, dkv_bytes = flops.flash_kernel_call("dkv", 8, 8, 16, 64, 2, False)
    assert (dq, dkv) == (1.5 * ops, 2 * ops)
    # dq: q, o, do read and dq written (4 x lq), k, v read (2 x lk)
    assert dq_bytes == 8 * 64 * (4 * 8 + 2 * 16) * 2 + 8 * 8 * 4
    # dkv: q, o, do read (3 x lq), k, v read and dk, dv written (4 x lk)
    assert dkv_bytes == 8 * 64 * (3 * 8 + 4 * 16) * 2 + 8 * 8 * 4


V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_one_flash_call_of_the_big_step_by_hand():
    """64 rows x 16 heads x 256 x 64 in bfloat16, the shape of every flash
    call in ``big-train-s256``: QK^T and PV are 2 x (2 x 256 x 256 x 64)
    operations a head = 16.8 M, x 1024 row-heads = 17.2 GFLOP; q, k, v and o
    are 4 x 64 x 16 x 256 x 64 x 2 B = 134.2 MB and the float32 row
    statistics 64 x 16 x 256 x 4 B = 1.05 MB.  On a v5e that is 0.087 ms of
    compute against 0.165 ms of memory traffic: memory-bound."""
    ops, bytes_ = flops.flash_kernel_call("fwd", 64 * 16, 256, 256, 64, 2,
                                          causal=False)
    assert ops == 2 * 2 * 256 * 256 * 64 * 16 * 64 == 17179869184
    assert bytes_ == 4 * 64 * 16 * 256 * 64 * 2 + 64 * 16 * 256 * 4 \
        == 135266304
    least, bound = flops.least_seconds(ops, bytes_, V5E)
    assert bound == "memory" and least == pytest.approx(0.1652e-3, rel=1e-3)
    assert ops / V5E["bf16_flops_per_s"] == pytest.approx(0.0872e-3, rel=1e-3)
    # the causal call needs half the operations and the same bytes
    half, same = flops.flash_kernel_call("fwd", 64 * 16, 256, 256, 64, 2,
                                         causal=True)
    assert (half, same) == (ops / 2, bytes_)
    # at 2048 the same kernel is compute-bound: 8 x 16 x 2048 x 2048 x 64
    long_ops, long_bytes = flops.flash_kernel_call("fwd", 128, 2048, 2048,
                                                   64, 2, causal=False)
    assert long_ops == 137438953472 and long_bytes == 135266304
    assert flops.least_seconds(long_ops, long_bytes, V5E) == \
        (pytest.approx(0.6977e-3, rel=1e-3), "compute")


def test_the_roofline_reader_takes_its_calls_from_the_trace():
    """87 forward calls of 1.2247 ms each (the recorded cut of a real
    ``big-train-s256`` trace): least 0.1652 ms a call -> 13.49 %."""
    from perfbench import layer_util

    r = tr.reduce(tr.load_json(os.path.join(
        HERE, "data", "trace_cut_big-train-s256.json.gz")))
    (kernel,) = r["kernels"]
    assert layer_util.flash_kernel_kind(kernel) == \
        ("fwd", 1024, 256, 256, 64, 2)
    assert kernel["calls"] == r["mosaic_calls"] == 87
    layer = {"kind": "train", "trace": r, "peaks": V5E}
    got = layer_util.train_attn_roofline(layer)
    by_hand = 100 * 87 * 0.16516e-3 / r["mosaic_s"]
    assert got == pytest.approx(by_hand, rel=1e-3) and 13 < got < 14
    # a backward pair, by its operands: q, k, v, do, o in; dq or dk, dv out
    t = ("bf16", (128, 2048, 64))
    stat = ("f32", (128, 2048, 128))
    assert layer_util.flash_kernel_kind(
        {"operands": (t,) * 5 + (stat,), "results": (t,)})[0] == "dq"
    assert layer_util.flash_kernel_kind(
        {"operands": (t,) * 5 + (stat,), "results": (t, t)})[0] == "dkv"
    assert layer_util.flash_kernel_kind(
        {"operands": (("s32", (64, 16)),), "results": (t,)}) is None


def test_one_ragged_step_of_the_served_model_by_hand():
    """64 lanes of ``transformer-base`` each decoding one token at position
    31 of a 28-token prompt, float32 pool: a lane reads (32 + 28) positions
    of K and V, 8 heads x 64 x 4 B each = 4096 B a position and layer, in 6
    layers: 64 x 6 x 60 x 4096 = 94.4 MB, and does 4 x 60 x 512 operations
    a layer = 47.2 MFLOP in all; 0.115 ms on a v5e, memory-bound."""
    base = {"n_layer": 6, "n_head": 8, "d_key": 64}
    ops, bytes_ = flops.ragged_need(base, 4, decoded=[(31, 28)] * 64,
                                    prefilled=[], chunk=32)
    assert bytes_ == 64 * 6 * 60 * 4096 == 94371840
    assert ops == 64 * 6 * 4 * 60 * 512 == 47185920
    least, bound = flops.least_seconds(ops, bytes_, V5E)
    assert bound == "memory" and least == pytest.approx(0.1152e-3, rel=1e-3)
    # a 40-token prompt prefilled in chunks of 32: the chunks end at 32, 40
    ops, bytes_ = flops.ragged_need(base, 4, [], prefilled=[40], chunk=32)
    assert bytes_ == 6 * (32 + 40) * 4096
    assert ops == 6 * 4.0 * (32 * 16.5 + 8 * (32 + 4.5)) * 512


def test_ragged_need_by_hand():
    cfg = dict(TINY, n_layer=2)                 # h*d = 8
    ops, bytes_ = flops.ragged_need(cfg, 4, decoded=[(0, 5), (3, 5)],
                                    prefilled=[], chunk=4)
    assert bytes_ == 2 * ((1 + 5) + (4 + 5)) * (2 * 8 * 4)
    assert ops == 2 * 4.0 * ((1 + 5) + (4 + 5)) * 8
    _, pre = flops.ragged_need(cfg, 4, [], prefilled=[6], chunk=4)
    assert pre == 2 * (4 + 6) * (2 * 8 * 4)    # chunk ends at 4, then at 6
    least, bound = flops.least_seconds(1e12, 1e9, {"bf16_flops_per_s": 1e14,
                                                   "hbm_bytes_per_s": 1e12})
    assert (least, bound) == (0.01, "compute")
    assert flops.least_seconds(1e9, 1e9, {"bf16_flops_per_s": 1e14,
                                          "hbm_bytes_per_s": 1e12})[1] == "memory"


# -- trace ------------------------------------------------------------------

KERNEL = ("%custom-call.4 = bf16[8,16,64]{2,1,0} custom-call(bf16[8,16,64]"
          "{2,1,0} %q, bf16[8,16,64]{2,1,0} %k, bf16[8,16,64]{2,1,0} %v), "
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          "{bf16[8,16,64]{2,1,0}}")


def hand_trace():
    ms = 1e6
    ops = [["fusion.1", 10 * ms, 4 * ms],
           ["while.2", 20 * ms, 10 * ms],
           ["fusion.3", 21 * ms, 3 * ms],           # inside the while
           [KERNEL, 25 * ms, 4 * ms],               # inside the while
           ["%custom-call.9 = f32[8,8]{1,0} custom-call(f32[8,8]{1,0} %p), "
            'custom_call_target="ConcatBitcast"', 30 * ms, 0.0],
           ["all-reduce.5", 40 * ms, 5 * ms],
           ["fusion.1", 60 * ms, 4 * ms]]
    modules = [["jit_step", 10 * ms, 35 * ms], ["jit_step", 60 * ms, 4 * ms]]
    host = [["pb:window", 0.0, 100 * ms], ["pb:fetch_loss", 45 * ms, 15 * ms],
            ["PjitFunction(step)", 0.0, 10 * ms]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]}]}


def test_opcode_is_parsed_from_the_instruction_text():
    fusion = ("%fusion.694 = f32[16384,1024]{1,0:T(8,128)S(1)} fusion(f32[16384]"
              "{0:T(1024)(128)(4,1)S(1)} %reshape.623, bf16[1024,32768]{1,0:"
              "T(8,128)(2,1)S(1)} %custom-call.211), kind=kOutput, calls=%f.9")
    call = ("%custom-call.211 = (bf16[64,16,256,64]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[64,16,256]{2,1,0}) custom-call(bf16[64,16,256,64]{3,2,1,0} "
            "%x), custom_call_target=\"tpu_custom_call\"")
    reduce_ = "%all-reduce.5 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0} %p)"
    assert tr.opcode(fusion) == "fusion" and not tr.is_mosaic_call(fusion)
    assert tr.opcode(call) == "custom-call" and tr.is_mosaic_call(call)
    assert tr.is_collective(reduce_) and not tr.is_collective(call)
    assert tr.short_name(fusion) == "fusion.694 = f32[16384,1024] fusion"
    assert tr.opcode("while.2") == "while"
    assert tr.opcode("copy-start.3") == "copy-start"


def test_interval_arithmetic():
    assert tr.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert tr.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    own = dict((n, t) for n, t in tr.self_times(hand_trace()["planes"][0]
                                                   ["lines"][0]["events"])
               if n != "fusion.1")
    bitcast = next(n for n in own if "ConcatBitcast" in n)
    assert own == {"while.2": 3e6, "fusion.3": 3e6, KERNEL: 4e6,
                   "all-reduce.5": 5e6, bitcast: 0.0}


def test_reduce_on_a_hand_built_trace():
    r = tr.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.023)      # 4 + 10 + 5 + 4 ms
    assert r["mosaic_s"] == pytest.approx(0.004) and r["mosaic_calls"] == 1
    t = ("bf16", (8, 16, 64))       # the compiler's bitcast is no kernel
    assert r["kernels"] == [{"results": (t,), "operands": (t, t, t),
                             "calls": 1, "seconds": pytest.approx(0.004)}]
    assert r["collective_s"] == pytest.approx(0.005)
    assert r["modules"] == 2
    assert r["launch_gaps_s"] == [pytest.approx(0.015)]
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.008) and "while.2" not in ops
    gaps = dict(r["idle_gaps"])
    assert gaps["pb:fetch_loss"] == pytest.approx(0.015)
    assert gaps["PjitFunction(step)"] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.023)
    assert tr.reduce({"planes": []}) == {}


def test_dump_and_load_round_trip(tmp_path):
    path = str(tmp_path / "cut.json.gz")
    tr.dump(hand_trace(), path, t0_ns=0, t1_ns=30e6)
    cut = tr.load_json(path)
    names = [e[0] for e in cut["planes"][0]["lines"][0]["events"]]
    assert names == ["fusion.1", "while.2", "fusion.3", KERNEL]


def test_load_xplane_reads_what_the_profiler_writes_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("pb:window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    trace = tr.load_xplane(found[0])
    assert tr._find_window(trace) is not None
    assert tr.reduce(trace) == {}        # a CPU trace has no device plane


@pytest.mark.parametrize("cell", ["big-train-s256", "base-serve-flood"])
def test_reduce_on_a_trace_recorded_on_the_chip(cell):
    path = os.path.join(HERE, "data", f"trace_cut_{cell}.json.gz")
    r = tr.reduce(tr.load_json(path))
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["mosaic_calls"] > 0 and 0 < r["mosaic_s"] < r["busy_s"]
    assert r["modules"] >= 1 and len(r["device_ops"]) == 10
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1] > 0
