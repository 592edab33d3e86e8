"""Two cells' CPU rehearsals (tests/rehearsal.py says why two a file and
where the next cell's goes): ``chat-rate`` (~70 s alone here) and
``thinklong`` (PR 54); the next cell's rehearsal opens a new file."""
from tests.rehearsal import cells, rehearse


@cells("chat-rate", "thinklong")
def test_the_new_cell_rehearses_on_the_cpu(tmp_path, cell, seed, reference,
                                           rate_rps):
    rehearse(tmp_path, cell, seed, reference, rate_rps)
