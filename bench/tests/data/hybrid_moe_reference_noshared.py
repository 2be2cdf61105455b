"""A broken twin of ``hybrid_moe_reference.py`` that leaves out the shared
expert: a run checked against it has to read ``correct: false``."""

from functools import partial

from harness import reference as ref
from harness.spec import load_code

_sound = load_code("tests/data/hybrid_moe_reference.py")
_layer = partial(_sound.attn_moe_layer, shared=False)


def logits(params, model, tokens, first, control=False):
    return ref.logits(params, model, tokens, first, control,
                      layers={"ssm": ref.ssm_layer, "attn": _layer})
