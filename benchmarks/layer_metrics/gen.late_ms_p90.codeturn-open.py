"""How late the load generator ran in the open-loop coding-turn mix: p90
over the window's requests of (actual - due) send time, from the
generator's own clock. About two requests a second, each a stream of
64-768 chunks read by this process's threads while it sends."""
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(sources):
    return sources["byname"].module_with(
        _HERE, "gen.late_ms_p90", "read").read(sources)
