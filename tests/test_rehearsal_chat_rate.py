"""One cell's CPU rehearsal, the first of a new file (tests/rehearsal.py
says why and where the next cell's goes): ``chat-rate`` (~70 s alone
here; the next cell's rehearsal may join it)."""
from tests.rehearsal import cells, rehearse


@cells("chat-rate")
def test_the_new_cell_rehearses_on_the_cpu(tmp_path, cell, seed, reference,
                                           rate_rps):
    rehearse(tmp_path, cell, seed, reference, rate_rps)
