"""Whether what the timed path produced is correct.

Two layers are held to it.

* Workflow: every instance started in the window has exactly one committed
  output of its entry and of its terminal function, the terminal output is
  what the stage computed for that instance, of the request's shape and in
  the vocabulary.
* Model stage: a sample of the served instances, drawn from the seed, goes
  through the plain float32 reference that the configuration names
  (``spec.reference_of``), once over each prompt with the
  tokens served after it.  At every served position the number read is
  how far the served token's reference logit lies below the reference's
  best, over the spread (standard deviation) of that position's logits.
  The widest of these over the sample is compared with the cell's limit.
  Under greedy decoding a served token is the program's own best, so the
  gap only opens where the program's logits depart from the reference's.

``failed`` counts the instances that miss either check.  Every instance
the run drove is held to it, a closed loop's lead-in too.

With ``control`` the control is judged as well, in the program's place:
the reference with float8 weight products puts its own best token first
at each served position, and that token's gap is held to the same limit
by the same code.  The control's verdict has to come out not correct.
"""

from __future__ import annotations

import random
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np


def _done(inst, function: str):
    return [r for r in inst.records if r.function == function and r.status == "done"]


def workflow_faults(window, entry: str) -> Dict[int, str]:
    """Instance index → what is wrong with it, for every instance started
    in the window that is not served correctly."""
    stage = window.stage
    vocab = stage.cfg.vocab
    out: Dict[int, str] = {}
    for inst in window.instances:
        i = inst.index
        term, ent = _done(inst, window.terminal), _done(inst, entry)
        if len(ent) != 1 or len(term) != 1:
            out[i] = f"{len(ent)} {entry} and {len(term)} {window.terminal} outputs committed"
            continue
        tokens = stage.outputs.get(i)
        if tokens is None:
            out[i] = "the stage never served it"
            continue
        if term[0].result != {"instance": i, "tokens": tokens}:
            out[i] = "the committed output is not what the stage computed"
            continue
        arr = np.asarray(tokens)
        if arr.shape != (stage.prompts, stage.new_tokens):
            out[i] = f"output shape {arr.shape}"
        elif arr.min() < 0 or arr.max() >= vocab:
            out[i] = "token ids outside the vocabulary"
    return out


def sample(window, seed: int, n: int, faults: Dict[int, str]) -> List:
    """``n`` served instances, drawn from the seed (the longest first when
    requests differ in length; here every request has the same length)."""
    ok = [i for i in window.instances if i.index not in faults]
    rng = random.Random(seed)
    return rng.sample(ok, min(n, len(ok)))


@dataclass
class Gaps:
    served: np.ndarray                    # [P, T] gap of each served token
    control: Optional[np.ndarray] = None  # [P, T] gap of the control's token


def gaps(window, entry: str, inst, reference: ModuleType,
         control: bool = False) -> Gaps:
    """Gaps over std at the served positions of one instance, by the
    ``reference`` module's ``logits``."""
    stage = window.stage
    prompts = np.asarray(_done(inst, entry)[0].result["prompts"], np.int32)
    served = np.asarray(stage.outputs[inst.index], np.int32)
    length = prompts.shape[1]
    tokens = np.concatenate([prompts, served[:, :-1]], axis=1)
    model = stage.model
    ref = reference.logits(stage.params, model, tokens, first=length - 1)
    best, std = ref.max(-1), ref.std(-1)

    def gap_of(tok):
        return (best - np.take_along_axis(ref, tok[..., None], -1)[..., 0]) / std

    out = Gaps(served=gap_of(served))
    if control:
        ctl = reference.logits(stage.params, model, tokens, first=length - 1,
                               control=True)
        out.control = gap_of(ctl.argmax(-1).astype(np.int32))
    return out


@dataclass
class Verdict:
    correct: bool
    failed: int
    numbers: Dict[str, Dict[str, float]] = field(default_factory=dict)
    reasons: Dict[int, str] = field(default_factory=dict)
    control: Optional["Verdict"] = None   # the control in the program's place


def _judge(window, faults: Dict[int, str], widest: Dict[int, float],
           limits: Dict) -> Verdict:
    """The verdict on the workflow's faults and the widest gap of each
    sampled instance."""
    faults = dict(faults)
    limit = float(limits["gap_over_std"])
    for i, w in widest.items():
        if not np.isfinite(w) or w > limit:
            faults[i] = f"served-token gap {w} over {limit}"
    attempted = len(window.instances)
    numbers = {
        "instances_wrong": {"value": len(faults), "limit": 0},
        "sampled_instances": {"value": len(widest),
                              "limit": min(int(limits["sample_instances"]),
                                           attempted)},
        "gap_over_std": {"value": max(widest.values(), default=0.0),
                         "limit": limit},
    }
    correct = (not faults and attempted > 0
               and len(widest) == numbers["sampled_instances"]["limit"])
    return Verdict(correct, len(faults), numbers, faults)


def check(window, entry: str, seed: int, limits: Dict, reference: ModuleType,
          control: bool = False) -> Verdict:
    """The verdict on a window, with ``reference`` the cell's plain
    reference (``Cell.reference``)."""
    faults = workflow_faults(window, entry)
    picked = sample(window, seed, int(limits["sample_instances"]), faults)
    got = {inst.index: gaps(window, entry, inst, reference, control=control)
           for inst in picked}
    verdict = _judge(window, faults, {i: float(np.max(g.served))
                                      for i, g in got.items()}, limits)
    if control:
        verdict.control = _judge(window, faults, {i: float(np.max(g.control))
                                                  for i, g in got.items()},
                                 limits)
    return verdict
