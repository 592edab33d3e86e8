"""The ``ouro-2p6b-ut4.chat`` cell's CPU rehearsal (tests/rehearsal.py)."""
from tests.rehearsal import cells, rehearse


@cells("chat-looped")
def test_the_new_cell_rehearses_on_the_cpu(tmp_path, cell, seed, reference,
                                           rate_rps):
    rehearse(tmp_path, cell, seed, reference, rate_rps)
