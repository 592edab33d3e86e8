"""How late the load generator ran in the open-loop long-context mix: p90
over the window's requests of (actual - due) send time, from the
generator's own clock. A request body here is a list of up to 28672 token
ids (~200 KB of JSON), built before the clock starts; the sender threads
still share the machine's cores with the server."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.late_ms_p90", "read").read(sources)
