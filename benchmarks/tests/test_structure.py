"""What the fuser plans for one application at w28: the counts the
cells' ``why`` lines rest on.  A PR that moves them is seen here,
without a chip."""

import structure
from families import PARAMS, family


def test_qft_w28():
    s = structure.summary(structure.plan_application(
        family("qft"), 28, PARAMS["qft"]))
    assert s["ops"] == 406
    assert s["windows"] == s["programs"] == s["kernel_windows"] == 26
    assert s["kernel_sweeps"] == 37
    assert s["kernel_sweeps_by_window"] == [5, 3, 3, 2, 3] + [1] * 21
    assert s["cross_tile_segments"] == 12
    assert s["fallbacks"] == []


def test_tfim_w28():
    s = structure.summary(structure.plan_application(
        family("tfim"), 28, PARAMS["tfim"]))
    assert s["ops"] == 109
    assert s["windows"] == s["programs"] == 7
    assert s["kernel_sweeps_by_window"] == [1, 1, 3, 11, 11, 1]
    assert s["kernel_sweeps"] == 28
    assert s["cross_tile_segments"] == 24
    assert s["fallbacks"] == [("no_sweep_gain", 13)]


def test_tfim_steps_repeat_their_programs():
    """The evolving ket re-runs the same structures every step."""
    fam = family("tfim")
    plan = fam.Plan(28, PARAMS["tfim"], 1)
    q = structure.PlanOnlyEngine(28)
    for i in range(3):
        fam.enqueue(q, plan, i, structure._no_spans)
        q.GetAmplitude(0)
    assert len(q.windows) == 21
    assert len({w["structure"] for w in q.windows}) == 7
