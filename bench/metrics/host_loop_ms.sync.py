"""Host time per round in the traced span outside the scheduler's
dispatch, device_get and compile phases: accounting, the recorder and
Python between fetching one chunk and dispatching the next."""


def read(facts):
    if facts.rounds == 0:
        return None
    inside = sum(facts.host_phase_s.get(k, 0.0) for k in ("dispatch", "device_get", "compile"))
    return 1000.0 * (facts.host_span_s - inside) / facts.rounds
