"""A whole run at a tiny size on the CPU, with the look for a chip
skipped: the sound program comes out correct, and with the timed path
broken underneath (or the control put in its place) `correct` comes out
false.  The limits are the cells' own."""
import jax
import pytest

import run
import tiny


def _run(monkeypatch, traffic, **overrides):
    monkeypatch.setattr(run, "load_cell",
                        lambda name: tiny.tiny_cell(traffic))
    return run.main(["--workload", "tiny", "--seed", str(2 ** 31 + 11),
                     "--seconds", "0.3"],
                    find_devices=lambda n: jax.devices()[:n],
                    overrides=overrides)


ENGINE = ["solve-t120", "grid64-t120"]


def _plant_unchanged(monkeypatch):
    from repro.core import afto

    real = afto.afto_step_aux

    def step(problem, hyper, state, active, axis=None):
        return state, real(problem, hyper, state, active, axis=axis)[1]

    monkeypatch.setattr(afto, "afto_step_aux", step)
    return None


def _plant_half_batch(monkeypatch):
    def plant(prog):
        import dataclasses

        f1 = prog.problem.f1

        def half(d, x1, x2, x3):
            n = d["xval"].shape[0] // 2
            return f1(dict(d, xval=d["xval"][:n], yval=d["yval"][:n]),
                      x1, x2, x3)
        prog.problem = dataclasses.replace(prog.problem, f1=half)
    return plant


def _plant_altered_answer(monkeypatch):
    def plant(prog):
        solve = prog.solve

        def altered(runs):
            res = solve(runs)
            res.history["gap_sq"] = res.history["gap_sq"] * 1.25
            return res
        prog.solve = altered
    return plant


@pytest.mark.parametrize("traffic", ENGINE)
def test_engine_sound_program_is_correct(monkeypatch, traffic):
    assert _run(monkeypatch, traffic)["correct"] is True


@pytest.mark.parametrize("traffic", ENGINE)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch",
                                   "altered_answer"])
def test_engine_broken_path_is_not_correct(monkeypatch, traffic, fault):
    plant = {"unchanged": _plant_unchanged, "half_batch": _plant_half_batch,
             "altered_answer": _plant_altered_answer}[fault](monkeypatch)
    res = _run(monkeypatch, traffic, **({"plant": plant} if plant else {}))
    assert res["correct"] is False


@pytest.mark.parametrize("traffic", ENGINE)
def test_engine_control_is_not_correct(monkeypatch, traffic):
    """The reference in bfloat16 put in the program's place."""
    assert _run(monkeypatch, traffic, reference="bf16")["correct"] is False


def test_calibrate_reads_program_and_control():
    """The limits' readings at the tiny size: the program on the CPU
    agrees with the reference to rounding, the control does not."""
    import calibrate

    rows = calibrate.main(["--workload", "tiny", "--seeds", "11-12",
                           "--control-seeds", "21"],
                          find_devices=lambda n: jax.devices()[:n],
                          cell=tiny.tiny_cell("solve-t120"))
    prog = [r for r in rows if r["kind"] == "program"]
    ctrl = [r for r in rows if r["kind"] == "control_bf16"]
    assert len(prog) == 2 and len(ctrl) == 1
    assert max(r["state_rel_err"] for r in prog) < 1e-5
    assert ctrl[0]["state_rel_err"] > 1e-3
