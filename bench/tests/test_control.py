"""The control: the reference computed with fp8 weights and
activations, put in the program's place, comes out not correct by the
run's own judgement (``run.result_line``), where the program comes out
correct (at the tiny size, with the tiny configuration's limit)."""
import calibrate
import run
import tiny


def test_fp8_control_is_not_correct(monkeypatch):
    import driver
    bench = tiny.on_cpu(monkeypatch)
    res = driver.run(tiny.cell(), 2**31 + 5, 2.0, False, t0=0.0,
                     report=lambda m: None, control=True)
    limit = tiny.TINY_CONFIG["correct"]["max_logit_err"]
    print(res["logit_err"], res["control_err"])
    assert res["logit_err"] <= limit < res["control_err"]
    assert run.result_line(bench, tiny.cell(), res, False)["correct"]
    ctl = run.result_line(bench, tiny.cell(),
                          calibrate.control_result(res), False)
    assert ctl["correct"] is False
    assert ctl["checks"]["logit_err"]["value"] == res["control_err"]
    assert ctl["checks"]["logit_gap"]["value"] == res["control_gap"]
