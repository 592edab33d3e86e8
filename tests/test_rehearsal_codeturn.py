"""Two cells' CPU rehearsals (tests/rehearsal.py says why two a file and
where the next cell's goes): ``codeturn`` (PR 58, ~135 s alone here: 12 s
to the engine, 65 s of check at the cell's own 8300- and 4700-token
prompts, 55 s of warm-up, window and drain) and ``agentthink`` (PR 60,
~90 s alone here: 10 s to the engine, 35 s of check at the cell's own
4300- and 4097-token prompts, 45 s of warm-up, window and drain). Marked
``slow``: the tier-1 run stood 84 s under its 1470 s limit when the cell was added (PR 57's run:
1386 s) and a seventh rehearsal does not fit under it; run it by hand after
touching the cell's files, ``pytest tests/test_rehearsal_codeturn.py -m
slow``. What tier-1 holds of the cell without it: the benchmark's own
tests (tests/test_benchmark_rehearsal.py), the served path against the
cell's reference and the cell's readers (tests/test_window_gqa_moe.py,
tests/test_ssm_groups_moe.py), its programs compiled for the v5e
(tests/test_tpu_lowering.py). An eighth rehearsal fits no better."""
import pytest

from tests.rehearsal import cells, rehearse


@pytest.mark.slow
@cells("codeturn", "agentthink")
def test_the_new_cell_rehearses_on_the_cpu(tmp_path, cell, seed, reference,
                                           rate_rps):
    rehearse(tmp_path, cell, seed, reference, rate_rps)
