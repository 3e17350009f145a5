"""Wire: of the bytes read off the wire (the ``wire_copy`` events' bytes),
the share the kernel wrote straight into the pooled buffer a piece lands
from (``TaskFlight.wire_direct_bytes``), over the window's requests: how
often the direct receive path engages. 1.0 less the body bytes that rode in
behind a response head and were copied once. A program that has no such
slot (the commit before) gives nothing to read."""


def read(obs):
    read_bytes = direct = 0
    seen = False
    for r in obs.window.requests:
        if r.ok and r.flight is not None \
                and hasattr(r.flight, "wire_direct_bytes"):
            seen = True
            direct += r.flight.wire_direct_bytes
            read_bytes += sum(n for _t, stage, _p, _par, n, _d
                              in list(r.flight.events)
                              if stage == "wire_copy")
    return direct / read_bytes if seen and read_bytes > 0 else None
