"""The looped dense configuration's programs (cell 13, ``ouro-2p6b-ut4``;
tests/lowering.py has the rule for a new configuration).

The loop is static branches of the dense decoder (models/llama.py): a
config that takes none of them traces the plain dense programs, to the
letter; a looped one compiles for the v5e with a Mosaic call a (step,
layer), weights indexed by layer and K/V by plane, nothing the size of the
region or of a weight shard copied.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.attention import REFERENCE
from tests.lowering import (
    assert_pinned,
    assert_prefill_programs,
    pinned,
    record,
)

CONFIG = "ouro-2p6b-ut4"
STEPS = 4


def _programs(c):
    """The lowered text of a batched prefill, fresh and continuing, and of
    a decode step of ``c`` at toy shapes (no compile)."""
    params = jax.eval_shape(lambda: llama.init_params(c, 0))
    ctx = jax.eval_shape(lambda: llama.init_ctx(c, 2, 64, jnp.float32))
    ring = jax.eval_shape(lambda: llama.init_ring(c, 2, 4, jnp.float32))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    out = {
        f"batch_prefill_S{span}": llama.batch_prefill.trace(
            c, params, ctx, i32(2, 16), i32(2), i32(2), i32(2), span,
            i32(2), attn=REFERENCE).lower().as_text()
        for span in (0, 64)}
    out["round_step"] = jax.jit(
        llama.round_step, static_argnums=0, static_argnames="attn").trace(
        c, params, ctx, ring, {}, i32(2), i32(2), i32(2), i32(),
        jax.ShapeDtypeStruct((2,), jnp.bool_), i32(2),
        jax.eval_shape(lambda: llama.stats_zero(c)),
        attn=REFERENCE).lower().as_text()
    return out


@pytest.mark.parametrize("program", ["batch_prefill_S0", "batch_prefill_S64",
                                     "round_step"])
def test_one_step_and_no_sandwich_norms_is_the_plain_dense_text(program):
    """(e) A config read as ``ouro`` with ONE pass, no sandwich norms and
    no gate takes no loop branch: the lowered text of its programs is the
    plain dense config's, byte for byte (which tests/test_lowering_dense.py
    pins to the parent's at the dense cells' shapes). A looped one's is
    not."""
    plain = ModelConfig.tiny(num_layers=3)
    same = ModelConfig.tiny_looped(loop_steps=1, sandwich_norms=False,
                                   exit_gate=False)
    assert not same.looped and same != plain
    assert _programs(same)[program] == _programs(plain)[program]
    assert _programs(ModelConfig.tiny_looped())[program] != (
        _programs(plain)[program])


# the looped round and the solo prefill of the first bucket (what the cell
# dispatches: one prompt a prefill) at 2 weight layers x 4 passes = 8
# planes, as PR 64 left them
LOOPED = {
    (CONFIG, 2): {
        "round_seal_n4_w4": "5e9c531fa66fd78a",
        "prefill_T128": "ddedcdbe5139b807",
    },
}


@pinned(LOOPED)
def test_the_looped_programs_keep_their_lowering(key, program):
    assert_pinned(LOOPED, key, program)


@pytest.mark.parametrize("program,mosaic", [("round_seal", 8),
                                            ("prefill", 8),
                                            ("prefill_cont", 8),
                                            ("batch_prefill", 8)])
def test_looped_programs_copy_no_region_and_no_weight_on_v5e(program,
                                                             mosaic):
    """Compiled for the v5e at 2 weight layers: ONE Mosaic call a (step,
    layer) (the decode kernel in the round, the fused prefill kernel at
    group 1: 16 query heads on 16 K/V heads of 128 pass
    ``flash_prefill``'s shape rule), the region (8 planes here, 192 in the
    cell) copied nowhere, every product reading its weight where it lies
    in the stack at EVERY pass (a pass that hoisted a slice of the stack
    would show as ``weight_copies``)."""
    rec = record(CONFIG, program, layers=2)
    assert rec["ok"], rec.get("error")
    assert rec["region_shard"] == [2 * STEPS, 16, 4, 1280, 128]
    assert rec["mosaic_calls"] == mosaic
    assert rec["region_copies"] == {"count": 0, "shapes": []}, rec
    assert rec["weight_copies"] == [], rec["weight_copies"]
    kernel = ("flash_decode_attention" if program == "round_seal"
              else "flash_prefill_attention")
    assert len(re.findall(rf"%{kernel}\S* = ", rec["text"])) == mosaic


def test_the_cell_reaches_one_solo_program_a_bucket():
    """Three lanes of 1280, and ONE prompt a prefill dispatch
    (``prefill_batch_max`` 1): a whole-model program of 192 layer bodies is
    75-110 s of a cold start and 18-20 s of a warm one (PERF.md section 6,
    PR 64), so the cell's engine options reach the solo program of a
    bucket, fresh and continuing, and no ``[2, T]`` one."""
    assert_prefill_programs(
        CONFIG, sorted((T, 1, cont) for T in (128, 256, 512, 1024, 2048, 4096)
                       for cont in (False, True)), slots=3, context=1280)
