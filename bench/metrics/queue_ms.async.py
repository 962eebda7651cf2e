"""Host time per aggregation event in the async scheduler's ``queue``
spans: popping the landing slots off the event queue (with any fault
handling) and re-arming the slots the step dispatched."""

from bench import scopes


def read(facts):
    return scopes.host_phase_ms(facts, "queue")
