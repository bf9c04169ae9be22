"""profiling subpackage (SURVEY.md §5.1/§5.5)."""
from .grapher import Grapher, grapher
from .pins import (PINS, IteratorsCheckerModule, PinsEvent, PinsModule,
                   TaskProfilerModule, TaskTimeModule, pins_is_active)
from .sde import (PENDING_TASKS, TASKS_ENABLED, TASKS_RETIRED, SDERegistry,
                  sde)
from .trace import Dictionary, Profile, ThreadStream

__all__ = [
    "PINS", "PinsEvent", "PinsModule", "pins_is_active",
    "TaskProfilerModule", "IteratorsCheckerModule", "TaskTimeModule",
    "Grapher", "grapher", "SDERegistry", "sde",
    "TASKS_ENABLED", "TASKS_RETIRED", "PENDING_TASKS",
    "Dictionary", "Profile", "ThreadStream",
]
