"""The circuit families with the parameters the configurations give them."""

import json
import os

import harness

PARAMS, LIMITS, CONFIGS = {}, {}, {}
for _name in sorted(os.listdir(os.path.join(harness.HERE, "configs"))):
    with open(os.path.join(harness.HERE, "configs", _name)) as _f:
        _cfg = json.load(_f)
    CONFIGS[_cfg["name"]] = _cfg
    PARAMS[_cfg["family"]] = dict(_cfg["circuit"], warmup_applications=1)
    LIMITS[_cfg["family"]] = _cfg["limits"]
FAMILIES = sorted(PARAMS)


def family(name):
    return harness.load_module("circuits", name)


def engine(stack, width, **kwargs):
    from qrack_tpu import create_quantum_interface
    from qrack_tpu.utils.rng import QrackRandom

    return create_quantum_interface(stack, width, rng=QrackRandom(7),
                                    rand_global_phase=False, **kwargs)


def issue(q, gates):
    """A gate list through the engine's gate methods."""
    for controls, matrix, target in gates:
        if controls:
            q.MCMtrx(controls, matrix, target)
        else:
            q.Mtrx(matrix, target)
