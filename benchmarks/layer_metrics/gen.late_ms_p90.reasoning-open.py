"""How late the load generator ran in the open-loop reasoning mix: p90
over the window's requests of (actual - due) send time, from the
generator's own clock. Up to 48 streams of one to two thousand chunks
each are read by this process's threads while it sends; the sender
threads share the machine's cores with the server."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.late_ms_p90", "read").read(sources)
