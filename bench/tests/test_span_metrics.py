"""The metrics that read the program's host spans, on hand-made planes:
the split of the chip's idle time by the innermost program span, and
the install rate from the install spans' bytes."""
import types

import pytest

import profile_reduce as pr
import spec as bspec

IDLE = ["device.idle.attention_pct", "device.idle.route_pct",
        "device.idle.install_pct", "device.idle.ffn_pct",
        "device.idle.step_other_pct", "device.idle.outside_pct"]
# the six parts of step_other, one for each span it holds
PARTS = ["device.idle.schedule_pct", "device.idle.sample_pct",
         "device.idle.logits_pct", "device.idle.step_self_pct",
         "device.idle.decode_self_pct", "device.idle.moe_self_pct"]


def _host(program=True, extra=()):
    """One step in a 1000 ns window, and the spans of ``extra``. With
    ``program`` false only the benchmark's own spans are there, as in a
    trace of a program that has none."""
    spans = [("bench.window", 0, 1000, {}), ("bench.step", 0, 950, {}),
             ("engine.decode_tokens", 120, 780, {})]
    if program:
        spans += [
            ("server.step", 50, 900, {"step": 0, "rows": 1}),
            ("server.schedule", 50, 70, {}),
            ("engine.decode", 130, 760, {"rows": 1}),
            ("engine.attention", 130, 170, {"layer": 0}),
            ("engine.moe", 300, 550, {"layer": 0}),
            ("engine.route", 300, 50, {"layer": 0}),
            ("expert_cache.install", 350, 150,
             {"layer": 0, "expert": 3, "bytes": 1000, "demand": 1}),
            ("expert_cache.install", 500, 100,
             {"layer": 0, "expert": 5, "bytes": 500, "demand": 1}),
            ("engine.ffn", 600, 200, {"layer": 0, "experts": 2, "rows": 1}),
            ("engine.logits", 850, 30, {}),
            ("server.sample", 900, 40, {"rid": 1}),
            # after the window: not counted
            ("expert_cache.install", 1200, 10,
             {"layer": 1, "expert": 0, "bytes": 7, "demand": 1})]
    spans += list(extra)
    return ("/host:CPU", [("python", spans)])


def _chip(n, ops):
    mods = [("jit__set_slot(4)", 550, 30, {}),
            ("jit__set_slot(4)", 590, 60, {})]
    return (f"/device:TPU:{n}",
            [("XLA Ops", [(f"op.{i}", a, b - a, {})
                          for i, (a, b) in enumerate(ops)]),
             ("XLA Modules", mods)])


# chip 0 is busy in [100,200] [400,500] [700,800]: idle 700 of 1000 ns
CHIP0 = [(100, 200), (400, 500), (700, 800)]


def _ctx(planes, nbytes=999_999):
    prof = pr.from_planes(planes)
    return types.SimpleNamespace(
        profile=prof,
        traced=types.SimpleNamespace(counts={"bytes": nbytes}))


def _read(name, ctx):
    return bspec.metric_reader(name).read(ctx)


@pytest.mark.parametrize("chips", [[CHIP0], [CHIP0, [(0, 300), (900, 1000)]]])
def test_buckets_sum_to_idle_share(chips):
    ctx = _ctx([_host()] + [_chip(i, ops) for i, ops in enumerate(chips)])
    idle = _read("device.idle_pct", ctx)
    parts = [_read(n, ctx) for n in IDLE]
    assert all(p is not None and p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(idle, abs=1e-9)
    other = [_read(n, ctx) for n in PARTS]
    assert all(p is not None and p >= 0 for p in other)
    assert sum(other) == pytest.approx(parts[4], abs=1e-9)


def test_gaps_are_cut_at_span_boundaries():
    ctx = _ctx([_host(), _chip(0, CHIP0)])
    got = {n: _read(n, ctx) for n in IDLE + PARTS}
    # the gap [200,400] straddles attention (to 300), route (to 350) and
    # an install; [500,700] an install (to 600) and the FFN
    assert got["device.idle.attention_pct"] == pytest.approx(10.0)
    assert got["device.idle.route_pct"] == pytest.approx(5.0)
    assert got["device.idle.install_pct"] == pytest.approx(15.0)
    assert got["device.idle.ffn_pct"] == pytest.approx(10.0)
    # schedule 50, moe 50, logits 30, decode 10, step 10+10, sample 40
    assert got["device.idle.step_other_pct"] == pytest.approx(20.0)
    assert got["device.idle.schedule_pct"] == pytest.approx(5.0)
    assert got["device.idle.moe_self_pct"] == pytest.approx(5.0)
    assert got["device.idle.logits_pct"] == pytest.approx(3.0)
    assert got["device.idle.decode_self_pct"] == pytest.approx(1.0)
    assert got["device.idle.step_self_pct"] == pytest.approx(2.0)
    assert got["device.idle.sample_pct"] == pytest.approx(4.0)
    # [0,50] and [950,1000]: the harness between steps
    assert got["device.idle.outside_pct"] == pytest.approx(10.0)


def test_benchmark_span_is_not_the_programs():
    import span_reduce
    prof = pr.from_planes([_host(), _chip(0, CHIP0)])
    split = span_reduce.idle_ns_by_span(prof)
    assert "engine.decode_tokens" not in split
    # inside engine.decode_tokens but past engine.decode: server.step
    assert split["server.step"] == pytest.approx(20.0)
    # a program without spans (only the benchmark's) reports nothing
    ctx = _ctx([_host(program=False), _chip(0, CHIP0)])
    assert all(_read(n, ctx) is None for n in IDLE + PARTS)
    assert _read("expert_install.span_gb_per_s", ctx) is None
    assert _read("device.idle_pct", ctx) is not None


def test_no_tpu_plane_gives_none():
    ctx = _ctx([_host(), ("/host:CPU:1", [("x", [("y", 0, 5, {})])])])
    for name in IDLE + PARTS + ["expert_install.span_gb_per_s"]:
        assert _read(name, ctx) is None, name


def test_install_rate_from_span_bytes(capsys):
    ctx = _ctx([_host(), _chip(0, CHIP0)], nbytes=1500)
    # 1000 + 500 bytes (not the install after the window) over the union
    # of the spans [350,600] and the slot writes [550,580] [590,650]
    assert _read("expert_install.span_gb_per_s", ctx) == pytest.approx(
        1500 / 300)
    err = capsys.readouterr().err
    assert "2, 1500 bytes" in err and "delta 1500" in err
    # the counters' delta is printed beside it, never read for the rate
    ctx = _ctx([_host(), _chip(0, CHIP0)], nbytes=1)
    assert _read("expert_install.span_gb_per_s", ctx) == pytest.approx(5.0)


def test_install_rate_clips_spans_and_writes_alike(capsys):
    # an install from 900 to 1100 (400 bytes) and its slot write from
    # 980 to 1040 cross the window's end: half the span's bytes, and
    # time to 1000, are the window's
    extra = [("expert_cache.install", 900, 200,
              {"layer": 1, "expert": 2, "bytes": 400, "demand": 1})]
    host = _host(extra=extra)
    chip = _chip(0, CHIP0)
    chip[1][1][1].append(("jit__set_slot(4)", 980, 60, {}))
    ctx = _ctx([host, chip], nbytes=1900)
    assert _read("expert_install.span_gb_per_s", ctx) == pytest.approx(
        (1500 + 200) / (300 + 100))
    # the printed check counts whole spans that start in the window
    assert "3, 1900 bytes" in capsys.readouterr().err
