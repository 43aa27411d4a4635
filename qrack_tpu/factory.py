"""Stack factory: runtime-composable simulator layer assembly.

Re-design of the reference factory (reference: include/qfactory.hpp:49
CreateQuantumInterface — recursive layer construction from a type
vector; :265 CreateArrangedLayersFull — boolean layer toggles; enum
QInterfaceEngine include/qinterface.hpp:37-132, QINTERFACE_OPTIMAL
:114-131). Layer names here:

  "tensor_network"     QTensorNetwork (circuit buffering + light cone)
  "noisy"              QInterfaceNoisy wrapper
  "unit" / "unit_multi" QUnit / QUnitMulti Schmidt factoring
  "stabilizer_hybrid"  Clifford tableau until forced off
  "stabilizer"         bare CHP tableau (Clifford-only)
  "unit_clifford"      QUnit factoring over per-subsystem tableaus
  "bdt" / "bdt_hybrid" QBdt decision tree / auto-switching hybrid
  "bdt_attached"       QBdt with dense leaf kets under the tree
                       (attached_qubits kwarg; default n//2 or
                       QRACK_QBDT_ATTACH_QB)
  "pager"              QPager sharded dense engine over the device mesh
  "hybrid"             QHybrid CPU<->TPU<->pager width switching
  "tpu"                QEngineTPU single-device dense engine
  "cpu"                QEngineCPU host oracle
  "sparse"             QEngineSparse map-style sparse state vector
  "turboquant"         QEngineTurboQuant block-compressed resident ket
  "turboquant_pager"   QPagerTurboQuant compressed ket sharded over the
                       device mesh (compressed ICI pair exchange)
  "route"              QRouted lazy per-job stack selection: the first
                       submitted QCircuit picks the representation
                       (route/, docs/ROUTING.md; QRACK_ROUTE pins it)
  "lightcone"          QLightCone circuit buffering: reads build
                       cone-width kets through the routed ladder, never
                       the full-width ket (lightcone/, docs/LIGHTCONE.md)

create_quantum_interface(layers, n) composes them top-down; OPTIMAL is
["unit", "stabilizer_hybrid", "hybrid"] — the reference's production
stack shape with the TPU-native dense bottom."""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Union

from . import resilience as _res
from . import telemetry as _tele

OPTIMAL = ("unit", "stabilizer_hybrid", "hybrid")
OPTIMAL_MULTI = ("unit_multi", "stabilizer_hybrid", "hybrid")

_TERMINAL = {"cpu", "tpu", "pager", "hybrid", "stabilizer", "bdt",
             "bdt_attached", "unit_clifford", "sparse", "turboquant",
             "turboquant_pager", "route", "lightcone"}


def _counted(name: str, fn: Callable) -> Callable:
    """Count stack instantiations per layer (telemetry: factory.create.*).
    The wrapper only runs at construction time, never per gate."""
    def make(n, **kw):
        if _tele._ENABLED:
            _tele.inc(f"factory.create.{name}")
        return fn(n, **kw)
    return make


# terminals that dispatch to the accelerator without their own failover
# logic (QHybrid fails over in place; cpu/stabilizer/... never dispatch)
_ACCEL_TERMINALS = {"tpu", "pager", "turboquant", "turboquant_pager"}


def touches_accelerator(layers: Union[str, Sequence[str]]) -> bool:
    """True when a layer spec's terminal dispatches to the accelerator
    (directly, or via QHybrid's width switch).  The serving layer uses
    this to classify sessions for breaker-aware load shedding before an
    engine exists; a live session is classified by its actual engine."""
    if isinstance(layers, str):
        if layers in ("optimal", "optimal_multi"):
            return True  # OPTIMAL terminates in "hybrid"
        layers = (layers,)
    term = layers[-1] if layers else ""
    return term in _ACCEL_TERMINALS or term == "hybrid"


def _maybe_resilient(name: str, fn: Callable) -> Callable:
    """Wrap a bare accelerator terminal in ResilientEngine when the
    resilience layer is active, so a factory-built stack gets the same
    TPU→CPU degradation QHybrid provides (construction-time failures
    included).  _ACTIVE is re-read per construction: enabling resilience
    after import still takes effect."""
    if name not in _ACCEL_TERMINALS:
        return fn

    def make(n, **kw):
        if not _res._ACTIVE:
            return fn(n, **kw)
        from .resilience.failover import ResilientEngine

        return ResilientEngine.build(fn, n, **kw)

    return make


def _terminal_factory(name: str, **opts) -> Callable:
    if name == "cpu":
        from .engines.cpu import QEngineCPU

        return lambda n, **kw: QEngineCPU(n, **{**opts, **kw})
    if name == "tpu":
        from .engines.tpu import QEngineTPU

        return lambda n, **kw: QEngineTPU(n, **{**opts, **kw})
    if name == "pager":
        from .parallel.pager import QPager

        return lambda n, **kw: QPager(n, **{**opts, **kw})
    if name == "hybrid":
        from .engines.hybrid import QHybrid

        return lambda n, **kw: QHybrid(n, **{**opts, **kw})
    if name == "stabilizer":
        from .layers.stabilizer import QStabilizer

        return lambda n, **kw: QStabilizer(n, **{**opts, **kw})
    if name == "bdt":
        from .layers.qbdt import QBdt

        return lambda n, **kw: QBdt(n, **{**opts, **kw})
    if name == "bdt_attached":
        import os

        from .layers.qbdt import QBdt

        def mk_attached(n, **kw):
            kw = {**opts, **kw}
            if "attached_qubits" not in kw:
                kw["attached_qubits"] = int(os.environ.get(
                    "QRACK_QBDT_ATTACH_QB", str(n // 2)))
            return QBdt(n, **kw)

        return mk_attached
    if name == "sparse":
        from .engines.sparse import QEngineSparse

        return lambda n, **kw: QEngineSparse(n, **{**opts, **kw})
    if name == "turboquant":
        from .engines.turboquant import QEngineTurboQuant

        return lambda n, **kw: QEngineTurboQuant(n, **{**opts, **kw})
    if name == "turboquant_pager":
        from .parallel.turboquant_pager import QPagerTurboQuant

        return lambda n, **kw: QPagerTurboQuant(n, **{**opts, **kw})
    if name == "unit_clifford":
        from .layers.qunitclifford import QUnitClifford

        return lambda n, **kw: QUnitClifford(n, **{**opts, **kw})
    if name == "route":
        # pseudo-terminal: construction is free (no engine exists until
        # routing picks one), and the chosen stack is built through
        # this same factory, so resilience wrapping and per-layer
        # creation counters apply to whatever the router instantiates
        from .route.router import QRouted

        return lambda n, **kw: QRouted(n, **{**opts, **kw})
    if name == "lightcone":
        # pseudo-terminal like "route": gates buffer host-side and the
        # cone-width stacks built at read time come back through this
        # factory (via the "route" spec), so resilience wrapping and
        # creation counters apply to whatever each cone builds
        from .lightcone.engine import QLightCone

        return lambda n, **kw: QLightCone(n, **{**opts, **kw})
    raise ValueError(f"unknown terminal layer {name!r}")


def build_factory(layers: Sequence[str], **opts) -> Callable:
    """Compose a constructor fn(n, **kw) from a top-down layer list
    (reference: CreateQuantumInterface recursion, qfactory.hpp:189-258)."""
    if not layers:
        raise ValueError("empty layer list")
    head, rest = layers[0], layers[1:]
    if head in _TERMINAL:
        if rest:
            raise ValueError(f"terminal layer {head!r} must be last")
        return _counted(head, _maybe_resilient(head, _terminal_factory(head, **opts)))
    below = build_factory(rest, **opts) if rest else None

    if head == "unit":
        from .layers.qunit import QUnit

        return _counted(head, lambda n, **kw: QUnit(n, unit_factory=below, **kw))
    if head == "unit_multi":
        from .layers.qunitmulti import QUnitMulti

        return _counted(head, lambda n, **kw: QUnitMulti(n, unit_factory=below, **kw))
    if head == "stabilizer_hybrid":
        from .layers.stabilizerhybrid import QStabilizerHybrid

        return _counted(head, lambda n, **kw: QStabilizerHybrid(n, engine_factory=below, **kw))
    if head == "tensor_network":
        from .layers.qtensornetwork import QTensorNetwork

        return _counted(head, lambda n, **kw: QTensorNetwork(n, stack_factory=below, **kw))
    if head == "bdt_hybrid":
        from .layers.qbdthybrid import QBdtHybrid

        return _counted(head, lambda n, **kw: QBdtHybrid(n, engine_factory=below, **kw))
    if head == "noisy":
        noise = opts.get("noise")
        if below is None:
            # terminal form: the trajectory-rng QNoisy engine over a CPU
            # oracle — branch choices come from (key, trajectory_id,
            # app_seq) counters, not the engine's sequential rng stream
            # (noise/channels.py, docs/NOISE.md)
            from .noise.channels import QNoisy

            model = opts.get("model")
            return _counted(head, lambda n, **kw: QNoisy(
                n, model=model, noise=noise, **kw))
        from .layers.noisy import QInterfaceNoisy

        return _counted(head, lambda n, **kw: QInterfaceNoisy(
            n, inner_factory=below, noise=noise, **kw))
    raise ValueError(f"unknown layer {head!r}")


def create_quantum_interface(layers: Union[str, Sequence[str]], qubit_count: int,
                             init_state: int = 0, **kwargs):
    """Build a simulator stack (reference: CreateQuantumInterface,
    include/qfactory.hpp:49).

    `layers` may be "optimal", "optimal_multi", a single layer name, or a
    top-down sequence, e.g. ["tensor_network", "unit",
    "stabilizer_hybrid", "hybrid"]."""
    if isinstance(layers, str):
        if layers == "optimal":
            layers = OPTIMAL
        elif layers == "optimal_multi":
            layers = OPTIMAL_MULTI
        else:
            layers = (layers,)
    opts = {k: kwargs.pop(k) for k in ("noise", "model", "devices",
                                       "n_pages", "dtype")
            if k in kwargs}
    # the stack's construction: device discovery, a pager's mesh, the
    # first fill (the span's count is the number of stacks built)
    with _tele.span("factory.create_interface"):
        factory = build_factory(tuple(layers), **opts)
        return factory(qubit_count, init_state=init_state, **kwargs)


def create_arranged_layers_full(nw: bool = False, md: bool = False, sd: bool = True,
                                sh: bool = True, bdt: bool = False, pg: bool = True,
                                tn: bool = False, hy: bool = True, oc: bool = True,
                                qubit_count: int = 1, **kwargs):
    """Boolean layer toggles matching the reference's pinvoke `init`
    signature (reference: include/qfactory.hpp:265
    CreateArrangedLayersFull; pinvoke init_count_type
    include/pinvoke_api.hpp:42): nw=noisy wrapper, md=multi-device QUnit,
    sd=Schmidt decomposition (QUnit), sh=stabilizer hybrid, bdt=binary
    decision tree hybrid, pg=paging, tn=tensor network, hy=hybrid,
    oc="OpenCL"→accelerator (TPU here)."""
    layers: List[str] = []
    if nw:
        layers.append("noisy")
    if tn:
        layers.append("tensor_network")
    if sd:
        layers.append("unit_multi" if md else "unit")
    if sh:
        layers.append("stabilizer_hybrid")
    if bdt:
        layers.append("bdt_hybrid")
    if hy:
        layers.append("hybrid")
    elif pg and oc:
        layers.append("pager")
    elif oc:
        layers.append("tpu")
    else:
        layers.append("cpu")
    return create_quantum_interface(layers, qubit_count, **kwargs)
