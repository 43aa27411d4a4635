"""The control of "How correct is decided": the same comparisons, one
precision below the configuration's float32, have to come out as not
correct.  Two controls: the plain reference holding its ket in
bfloat16, and the program's own narrower plane type."""

import numpy as np
import pytest

import harness
import reference
from families import CONFIGS, FAMILIES, LIMITS, PARAMS, family

WIDTH = 12


def _near(x, width):
    return [x] + [x ^ (1 << b) for b in range(width)] + \
        [x ^ (1 << b) ^ (1 << ((b + 5) % width)) for b in range(width)]


@pytest.mark.parametrize("name", FAMILIES)
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_reference_in_bfloat16_is_not_correct(name, seed):
    fam = family(name)
    x = int(np.random.default_rng(seed).integers(1, 1 << WIDTH))
    gates = fam.gates(WIDTH, PARAMS[name])
    ys = _near(x, WIDTH)
    want = [fam.amplitude(WIDTH, PARAMS[name], x, y) for y in ys]

    sound = harness.Checks(LIMITS[name])
    state = reference.run(WIDTH, gates, x, precision="float32")
    assert sound.amplitudes("float32", state[ys], want)

    control = harness.Checks(LIMITS[name])
    state = reference.run(WIDTH, gates, x, precision="bfloat16")
    assert not control.amplitudes("bfloat16", state[ys], want)
    assert not control.correct


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_program_in_bfloat16_is_not_correct(config):
    """The program's own path: the engine the configuration names, at
    its rehearsal width, with its planes held in bfloat16."""
    import jax.numpy as jnp

    from families import engine

    cfg = CONFIGS[config]
    name, width = cfg["family"], cfg["rehearse_qubit_count"]
    fam = family(name)
    plan = fam.Plan(width, PARAMS[name], 5)
    q = engine(cfg["stack"], width, dtype=jnp.bfloat16,
               **cfg["engine"]["kwargs"])
    assert type(q).__name__ == cfg["engine"]["class"]
    checks = harness.Checks(cfg["limits"])
    spans = harness.Spans()
    try:
        for k in range(1):
            fam.warmup(q, plan, k, spans, checks)
        fam.start(q, plan, spans)
        fam.enqueue(q, plan, 0, spans)
        q.GetAmplitude(fam.read_index(plan, 0))
        fam.final_check(q, plan, 0, spans, checks)
    finally:
        # QPager keys its SetPermutation program without the planes' type
        # (PERF.md section 7): a float32 pager of the same width and mesh,
        # later in this process, would be handed this one's bfloat16 fill
        from qrack_tpu.parallel import pager

        pager._PROGRAMS.clear()
    assert not checks.correct
    assert any(r.get("limit_key") == "amplitude_rel_err" and not r["ok"]
               for r in checks.records)
