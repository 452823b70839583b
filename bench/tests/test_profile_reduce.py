"""The reduction from a profiler trace to busy time, kernel time and
idle gaps, on a small recorded trace and on a hand-made one."""
import time

import jax
import jax.numpy as jnp
import pytest

import profile_reduce as pr


def _planes():
    dev = "/device:TPU:0"
    ops = [("fusion.1", 100, 50, {"hlo_module": "jit__grouped_ffn"}),
           ("custom-call.2", 150, 30, {"hlo_module": "jit_paged_attention"}),
           ("fusion.1", 400, 100, {"hlo_module": "jit__grouped_ffn"}),
           ("copy.9", 900, 200, {"hlo_module": "jit_x"})]  # ends past window
    modules = [("jit__grouped_ffn(123)", 95, 60, {}),
               ("jit_paged_attention(7)", 150, 30, {}),
               ("jit__grouped_ffn(123)", 395, 110, {}),
               ("jit__grouped_ffn_other(5)", 600, 10, {}),
               ("jit__set_slot(9)", 60, 20, {})]
    host = [("bench.window", 0, 1000, {}), ("bench.step", 0, 600, {}),
            ("engine.decode_tokens", 10, 580, {}),
            ("DevicePut", 20, 45, {}),
            ("tpu::System::TransferToDevice", 62, 10, {}),
            ("DevicePut", 880, 5, {}),          # a step's input: no transpose
            ("tpu::System::TransferToDevice", 882, 2, {}),
            ("bench.step", 600, 400, {})]
    return [(dev, [("XLA Ops", ops), ("XLA Modules", modules),
                   ("Steps", [("s", 0, 10, {})])]),
            ("/host:CPU", [("python", host),
                           ("worker", [("Transpose::Execute", 30, 10, {}),
                                       ("Transpose::Execute", 990, 40, {})])])]


def test_busy_and_gaps_by_hand():
    prof = pr.from_planes(_planes())
    assert prof.window == (0.0, 1000.0)
    # busy: [100,180] + [400,500] + [900,1000] (clipped to the window)
    assert pr.busy_ns(prof) == 80 + 100 + 100
    # program runs by function name, and host spans, clipped to the window
    assert pr.module_ns(prof, "_grouped_ffn") == (170.0, 2)
    assert pr.module_ns(prof, "paged_attention") == (30.0, 1)
    # install: DevicePut [20,65] (a transpose inside) and its transfer
    # [62,72] + transposes [30,40] and [990,1000] (clipped) + the slot
    # write [60,80] on the chip; not the small hand-over at 880
    assert pr.install_ns(prof) == (60.0 + 10.0, 1)
    gaps = pr.idle_gaps(prof)
    # gaps: [0,100] [180,400] [500,900]; named by the innermost host span
    assert [round(g * 1e9) for _, g in gaps] == [400, 220, 100]
    assert gaps[0][0] == "bench.step"        # t=700: second step only
    assert gaps[1][0] == "engine.decode_tokens"
    top = pr.top_ops(prof)
    assert top[0][0] == "fusion.1"
    assert abs(top[0][1] - 150e-9) < 1e-18


def test_no_window_span_is_an_error():
    planes = [("/host:CPU", [("python", [("bench.step", 0, 5, {})])])]
    with pytest.raises(ValueError):
        pr.from_planes(planes)


def test_recorded_trace(tmp_path):
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(pr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    prof = pr.load(str(tmp_path))
    w = prof.window[1] - prof.window[0]
    assert w >= 1e7                      # the 10 ms sleep is inside
    steps = [e for e in prof.host if e.name == "bench.step"]
    assert len(steps) == 3
    assert all(prof.window[0] <= e.start and e.end <= prof.window[1]
               for e in steps)
    # a CPU trace has no TPU plane: nothing is counted as device time
    assert pr.busy_ns(prof) == 0.0
