"""A run with the timed path broken underneath comes out not correct: one
case for each fault the cell can have (a single chip has no exchange
between chips to leave out), and a run that compiles inside its window."""

import bench_testroot
import jax.numpy as jnp
import pytest

from repro.apps.fd2d import FDWave
from repro.models import LM


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_testroot.make_root(tmp_path_factory.mktemp("bench"))


def _decode_fault(kind):
    sound = LM.paged_greedy_step

    def broken(self, params, tokens, cache):
        nxt, logits, new = sound(self, params, tokens, cache)
        if kind == "state_unchanged":        # the step writes no KV
            return nxt, logits, dict(cache, len=new["len"],
                                     pos_pages=new["pos_pages"])
        if kind == "token_altered":
            return (nxt + 1) % self.cfg.vocab_size, logits, new
        # half the batch left out: every other slot's token is never made
        return nxt.at[1::2].set(0), logits, new
    return broken


@pytest.mark.parametrize("kind,cell", [
    ("state_unchanged", "tiny.chat"), ("token_altered", "tiny.chat"),
    ("half_batch", "tiny.offline")])      # a full batch every step
def test_serve_fault_is_not_correct(root, kind, cell, monkeypatch):
    monkeypatch.setattr(LM, "paged_greedy_step", _decode_fault(kind))
    out = bench_testroot.run_cell(root, cell)
    assert not out["correct"]
    assert not out["rec"]["checks"]["served_gap_mean"]["ok"]


def test_compile_inside_the_window_is_not_correct(root, monkeypatch):
    """A prompt length that set-up did not warm compiles its prefill when
    it arrives, inside the window."""
    from bench import traffic

    warmed = traffic.prompt_lengths
    monkeypatch.setattr(traffic, "prompt_lengths",
                        lambda mix, seconds: warmed(mix, seconds)[:-1])
    out = bench_testroot.run_cell(root, "tiny.chat")
    assert not out["correct"]
    compiles = out["rec"]["checks"]["window_compiles"]
    assert compiles["value"] > 0 and not compiles["ok"]
    assert out["rec"]["checks"]["served_gap_mean"]["ok"]


def _step_fault(kind):
    def broken(self):
        self.current_time += self.dt
        self.n_steps = getattr(self, "n_steps", 0) + 1
        if kind == "state_unchanged":        # u(t + dt) = u(t)
            self.o_u3._rebind(self.o_u1.data)
        else:
            self.fd2d(self.o_u1, self.o_u2, self.o_u3)
            u3 = self.o_u3.data
            if kind == "answer_altered":
                u3 = u3.at[7, 11].add(1.0)
            elif kind == "early_step_altered":   # one step, then sound ones
                u3 = u3.at[7, 11].add(1.0 if self.n_steps == 5 else 0.0)
            else:                           # half the rows left out
                u3 = u3.at[u3.shape[0] // 2:].set(self.o_u2.data[
                    u3.shape[0] // 2:])
            self.o_u3._rebind(jnp.asarray(u3))
        self.o_u2.swap(self.o_u3)
        self.o_u1.swap(self.o_u2)
    return broken


@pytest.mark.parametrize("kind,caught", [
    ("state_unchanged", {"run_rel_err", "last_step_rel_err"}),
    ("answer_altered", {"run_rel_err", "last_step_rel_err"}),
    ("half_batch", {"run_rel_err", "last_step_rel_err"}),
    ("early_step_altered", {"run_rel_err"})])
def test_solver_fault_is_not_correct(root, kind, caught, monkeypatch):
    monkeypatch.setattr(FDWave, "timestep", _step_fault(kind))
    out = bench_testroot.run_cell(root, "fdtiny.wave", seconds=1.0)
    assert not out["correct"]
    checks = out["rec"]["checks"]
    assert {k for k, c in checks.items() if not c["ok"]} == caught
