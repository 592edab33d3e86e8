"""Two cells' CPU rehearsals, a long one with a short one
(tests/rehearsal.py says why and where the next cell's goes): ``longdoc``
(188.5 s in the driver's run of PR 49's tree) and ``reasoning`` (92.1 s)."""
from tests.rehearsal import cells, rehearse


@cells("longdoc", "reasoning")
def test_the_new_cell_rehearses_on_the_cpu(tmp_path, cell, seed, reference,
                                           rate_rps):
    rehearse(tmp_path, cell, seed, reference, rate_rps)
