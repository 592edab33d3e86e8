"""The Mamba-1 + attention configuration's programs compiled for
compile-only v5e devices (cell 9: ``jamba2-3b``; tests/lowering.py has the
rule for a new configuration)."""
import re

import pytest

import jax
import jax.numpy as jnp

from tests.lowering import (
    assert_prefill_programs,
    one_v5e,
    record,
    serving_precision,
)

# the Mamba-1 + attention cell's programs at the WHOLE model's depth (28
# layers unrolled, 7.4 GB of arguments; compiled, PR 51: the round 0.091 GB
# of temporaries in ~16 s, the ``[2, 2048]`` fresh prefill 1.34 GB in ~26 s)
M1_TEMP_CEILING = {"round_seal": 0.15e9, "batch_prefill": 1.6e9}

# the full-depth round's digest
ROUND_LOWERING = "85ce2cc7f19b7d4e"


@pytest.mark.parametrize("name", sorted(M1_TEMP_CEILING))
def test_selective_scan_programs_relayout_neither_the_state_nor_a(name):
    """The fused round and the fresh ``[2, 2048]`` prefill at the published
    widths and depth (region ``[2, 1, 97, 4096, 128]``, 26 ``[97, 16,
    5120]`` float32 states and ``[97, 3, 5120]`` windows). Every decode
    step rewrites the live lanes of 26 state leaves in
    place (the step kernel aliases its state operand; the leaves ride the
    round's carry), a prefill chunk reads its lane's state and writes it
    in one tail pass: no synchronous ``copy`` (a relayout) of a state leaf
    or of ``A`` [16, 5120] held channels-minor, no copy the size of the
    K/V region, temporaries under their ceiling, and the kernels are
    there: 26 ``m1_step`` calls beside the two attention layers' in the
    round, the scan kernel in the prefill. XLA's memory-space assignment
    may park ONE state leaf in fast memory around the round's loop (an
    async copy-start / copy-done pair in and one out: 64 MB a round of
    four steps beside 24 GB of weight reads): that is its own decision
    and moves no layout."""
    rec = record("jamba2-3b", name, width=2048)
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [2, 1, 97, 4096, 128]
    relayouts = [l for l in rec["text"].splitlines() if re.search(
        r"= f32\[(97,)?16,5120\]\S* copy\(", l)]
    assert not relayouts, relayouts[:3]
    assert not [s for s in rec["region_copies"]["shapes"]
                if not s.startswith("f32[97,16,5120]")], rec
    assert rec["region_copies"]["count"] <= 2, rec
    assert rec["temp_bytes"] < M1_TEMP_CEILING[name], rec["temp_gb"]
    assert 7.3 < rec["argument_gb"] < 7.5
    if name == "round_seal":
        assert rec["mosaic_calls"] == 26 + 2
        assert rec["lowered_sha256"] == ROUND_LOWERING
        # x_proj's 192 columns (dt rank 160 + 2 x 16) for 96 lanes
        assert "bf16[96,192]" in rec["text"]
    else:
        assert rec["mosaic_calls"] >= 26
        # a block of the looped first half: in_proj over 512 rows
        assert "bf16[512,10240]" in rec["text"]


def test_selective_scan_kernels_compile_at_the_published_widths():
    """Both kernels of ops/mamba1.py alone, through Mosaic for the v5e
    (~3 s): a 256-position scan block of one lane and a decode step over
    96 lanes' states in place, at inner 5120 and 16 state columns."""
    from dynamo_tpu.ops import mamba1

    one = one_v5e()
    sd = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    T, I, N, L = 256, 5120, 16, 96
    bf = jnp.bfloat16
    with serving_precision():
        jax.jit(mamba1.scan_pallas).lower(
            sd((T, I), bf), sd((T, I)), sd((T, N), bf), sd((T, N), bf),
            sd((N, I)), sd((I,)), sd((N, I))).compile()
        step = jax.jit(mamba1.scan_step_pallas, donate_argnums=(6,)).lower(
            sd((L, I), bf), sd((L, I)), sd((L, N), bf), sd((L, N), bf),
            sd((N, I)), sd((I,)), sd((L + 1, N, I)), sd((L,), jnp.int32),
            sd((1,), jnp.int32)).compile()
    text = step.as_text()
    assert "m1_step" in text
    # the state goes in and comes out in one buffer: no copy of it
    assert not re.search(r"= f32\[97,16,5120\]\S* copy\(", text)


def test_chat_rate_cell_keeps_nine_prefill_programs():
    """4 buckets x {1, 2} lanes fresh (the mix's prompts are one chunk),
    and continuing programs only where the check's long prompt goes."""
    assert_prefill_programs(
        "jamba2-3b", slots=96, context=4096, continuing=(False,), expected=[
            (T, lanes, False) for T in (256, 512, 1024, 2048)
            for lanes in (1, 2)])
