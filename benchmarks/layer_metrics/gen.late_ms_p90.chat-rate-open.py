"""How late the load generator ran in the open-loop chat-rate mix: p90
over the window's requests of (actual - due) send time, from the
generator's own clock. Several requests a second, each a stream of
32-768 chunks read by this process's threads while it sends; the sender
threads share the machine's cores with the server."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.late_ms_p90", "read").read(sources)
