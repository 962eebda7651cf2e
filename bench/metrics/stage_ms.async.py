"""Host time per aggregation event in the async scheduler's ``stage``
span: building the step's argument tuple, each host array staged to the
device."""

from bench import scopes


def read(facts):
    return scopes.host_phase_ms(facts, "stage")
