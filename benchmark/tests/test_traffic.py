"""The closed-loop generators and the per-step token count."""
import json
import os

import pytest

from benchmark.kinds.closed_loop import Loop, Plan, pick_sample
from benchmark.tests import tiny

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["decode-heavy", "prefill-heavy"])
def test_every_seed_sends_the_cells_own_length_set(mix):
    t = _traffic(mix)
    want = sorted(zip(t["prompt_lens"], t["output_lens"]))
    for seed in (0, 7, 2**31 + 5):
        plan = Plan(t, 50257, seed)
        got = sorted((len(plan.next(c).prompt), plan.pairs[c][1])
                     for c in range(plan.n))
        assert got == want
        # and again on every client's second request
        got2 = sorted((len(s.prompt), s.max_new)
                      for s in (plan.next(c) for c in range(plan.n)))
        assert got2 == want


def test_same_seed_same_ids_other_seed_other_ids_same_work():
    """The seed chooses token ids alone: lengths, their order over the
    clients and the ramp's cuts are the cell's, under every seed."""
    t = _traffic("prefill-heavy")
    a, b, c = Plan(t, 50257, 11), Plan(t, 50257, 11), Plan(t, 50257, 12)
    sa, sb, sc = a.next(3, cut=True), b.next(3, cut=True), c.next(3, cut=True)
    assert sa.prompt == sb.prompt and sa.max_new == sb.max_new
    assert sa.prompt != sc.prompt
    assert (len(sa.prompt), sa.max_new) == (len(sc.prompt), sc.max_new)
    assert a.pairs == c.pairs == list(zip(t["prompt_lens"], t["output_lens"]))
    assert sorted(t["ramp_fractions"]) == [(k + 1) / 64 for k in range(64)]


def test_length_sets_are_what_the_cells_say():
    d, p = _traffic("decode-heavy"), _traffic("prefill-heavy")
    assert len(d["prompt_lens"]) == d["clients"] == 64
    assert len(p["prompt_lens"]) == p["clients"] == 64
    assert set(d["output_lens"]) == {128}
    assert 32 <= min(d["prompt_lens"]) and max(d["prompt_lens"]) <= 96
    assert 64 <= min(p["prompt_lens"]) and max(p["prompt_lens"]) <= 768
    assert 16 <= min(p["output_lens"]) and max(p["output_lens"]) <= 64
    assert max(a + b for a, b in zip(p["prompt_lens"],
                                     p["output_lens"])) < 1024
    assert sorted(p["prompt_lens"])[32] in range(250, 265)
    assert sorted(d["prompt_lens"])[32] in range(62, 67)


def _tiny_engine(seed=5):
    from benchmark.harness.cells import load_module
    fam = load_module(os.path.join(BENCH, "families", "gpt2.py"), "fam_gpt2")
    import jax
    eng, _ = fam.build_engine(tiny.GPT2, seed, jax.devices()[:1])
    return eng


def test_tokens_counted_per_step_add_up():
    """Tokens counted at the steps = tokens in finished streams + those of
    streams still in flight (prompt tokens fed + tokens emitted)."""
    eng = _tiny_engine()
    plan = Plan(tiny.LOOP, tiny.GPT2["vocab_size"], 9)
    loop = Loop(eng, eng.serve_config.prefill_chunk)
    for c in range(plan.n):
        loop.submit(plan.next(c))
    for _ in range(70):
        for s in loop.step()["ended"]:
            loop.submit(plan.next(s.client))
    counted = sum(r["tokens"] for r in loop.steps)
    emitted = sum(r["emitted"] for r in loop.steps)
    # a stream that got its first token has fed its whole prompt and then
    # one token per further token; one still prefilling has fed `ctx`
    fed = sum(s.ctx for s in loop.done + loop.live)
    assert counted == fed
    assert emitted == sum(len(s.handle.tokens) for s in loop.done + loop.live)
    finished = [s for s in loop.done]
    assert finished and all(
        s.ctx == len(s.prompt) + s.max_new - 1 for s in finished)
    assert loop.mirror_corrections == 0
    widths = {r["width"] for r in loop.steps}
    assert widths <= {1, eng.serve_config.prefill_chunk}


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    class S:
        def __init__(self, p, o):
            self.prompt = [0] * p
            self.handle = type("H", (), {"tokens": [0] * o})()
    done = [S(p, 4) for p in (5, 9, 30, 7, 8, 6)]
    a, b = pick_sample(done, 3, 1), pick_sample(done, 3, 1)
    assert [len(s.prompt) for s in a] == [len(s.prompt) for s in b]
    assert len(a[0].prompt) == 30 and len(a) == 3
    assert pick_sample([], 3, 1) == []
