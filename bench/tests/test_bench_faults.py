"""The comparison that decides ``correct`` must fail what it guards
against.  At a size the CPU holds (width 128), a whole run is driven with
the timed path broken underneath, once for each fault a served cell can
have, and ``correct`` comes out false.  The control, the reference with
float8 weight products, comes out not correct too: put in the program's
place, where it decodes greedily, and judged beside a sound run."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_smoke  # noqa: E402
from harness import check, cli, reference  # noqa: E402
from harness.spec import load_cell  # noqa: E402
from harness.stage import Stage  # noqa: E402
from repro.serve import engine  # noqa: E402

CELL = "qa-mamba2-370m.short-burst"
SEED = 2**31 + 5


@pytest.fixture
def root(tmp_path, monkeypatch):
    restore = bench_smoke.env_cache(monkeypatch, tmp_path)
    yield bench_smoke.build(tmp_path / "checkout", d_model=128, new_tokens=8)
    restore()


def _state_unchanged(monkeypatch):
    real = engine.decode
    monkeypatch.setattr(engine, "decode",
                        lambda p, c, tok, cache: (real(p, c, tok, cache)[0], cache))


def _half_batch(monkeypatch):
    real = Stage.generate

    def half(self, prompt):
        out = real(self, prompt[: prompt.shape[0] // 2])
        return jnp.concatenate([out, out], axis=0)
    monkeypatch.setattr(Stage, "generate", half)


def _token_altered(monkeypatch):
    real = Stage.generate

    def altered(self, prompt):
        out = real(self, prompt)
        return out.at[:, 0].set((out[:, 0] + 1) % self.cfg.vocab)
    monkeypatch.setattr(Stage, "generate", altered)


def _control_in_place(monkeypatch):
    def greedy(self, prompt):
        toks = np.asarray(prompt)
        for _ in range(self.new_tokens):
            lg = reference.logits(self.params, self.model, toks,
                                  first=toks.shape[1] - 1, control=True)
            toks = np.concatenate([toks, lg[:, -1].argmax(-1)[:, None]], 1)
        return jnp.asarray(toks[:, prompt.shape[1]:], jnp.int32)
    monkeypatch.setattr(Stage, "generate", greedy)


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch,
                                   _token_altered, _control_in_place])
def test_a_broken_timed_path_is_not_correct(fault, root, monkeypatch, capsys):
    if fault is not None:
        fault(monkeypatch)
    out, _ = bench_smoke.run(root, CELL, SEED, capsys)
    assert out["correct"] is (fault is None)
    gap = out["check"]["gap_over_std"]
    assert (gap["value"] > gap["limit"]) is (fault is not None)


def test_control_reads_above_the_limit(root):
    cli.setup_jax()
    cell = load_cell(CELL, root)
    driver = cli.prepare(cell, SEED)
    w = driver.window(cell.traffic["arrivals"], SEED, 2.0)
    v = check.check(w, "sort", SEED, cell.limits, cell.reference, control=True)
    assert v.correct and not v.control.correct
    assert v.control.failed > 0
    assert v.numbers["gap_over_std"]["value"] < bench_smoke.TEST_LIMIT \
        < v.control.numbers["gap_over_std"]["value"]
