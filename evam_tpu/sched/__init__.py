"""SLO-aware scheduling: admission control, priority classes, load
shedding — the QoS layer between REST and the shared batch engines.

Three cooperating parts (see each module's docstring):

* ``sched.admission`` — AdmissionController: reject over-capacity
  starts at the REST edge (503 + Retry-After) using a capacity model
  driven by the PR-1 stage clock;
* ``sched.classes``   — priority classes (realtime|standard|batch),
  SchedConfig (the EVAM_SCHED_* knob set), and ClassQueues (the
  per-class replacement for the engine's single FIFO, drained
  realtime-first with a starvation-proof weighted pick);
* ``sched.shedder``   — per-class staleness budgets enforced at
  dispatch: stale frames shed oldest-first (freshest-frame-wins),
  futures failed loudly as ShedError.

``EVAM_SCHED=off`` disables the whole layer: admission admits
everything, and every engine's class queues run as one FIFO (every
submit joins ``standard``, nothing is shed).
"""

from evam_tpu.sched.admission import (
    CLASS_HEADROOM,
    AdmissionController,
    AdmissionError,
)
from evam_tpu.sched.classes import (
    DEFAULT_PRIORITY,
    PRIORITIES,
    ClassQueues,
    SchedConfig,
    validate_priority,
)
from evam_tpu.sched.shedder import Shedder, ShedError

__all__ = [
    "CLASS_HEADROOM",
    "AdmissionController",
    "AdmissionError",
    "ClassQueues",
    "DEFAULT_PRIORITY",
    "PRIORITIES",
    "SchedConfig",
    "Shedder",
    "ShedError",
    "validate_priority",
]
