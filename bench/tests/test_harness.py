"""A whole run at CPU size through a test-only path: the harness's look for
a chip is skipped, everything else is the command's own code. A sound run
is correct; a token altered where the engine produces it is not; and the
bfloat16 control reads above the program on the same prompts and tokens."""

import json
from pathlib import Path

import pytest

from bench import harness

DATA = Path(__file__).resolve().parent / "data"
# At this size on the CPU every float32 matmul is exact float32, so the
# program's greedy tokens are the reference's own (gaps read 0 on every
# seed tried); the limits only have to sit above 0.
TINY_LIMITS = {"max_gap": 1e-4, "mean_gap": 1e-6}


def _cell(kind: str, limits=None) -> harness.Cell:
    name = "small-chat-open" if kind == "open" else "small-long-decode"
    real = harness.load_cell(name)
    return harness.Cell(
        name=name, config=json.loads((DATA / "gpt2-tiny.json").read_text()),
        traffic=json.loads((DATA / f"{kind}-tiny.json").read_text()),
        limits=limits or TINY_LIMITS, chips=1, end_to_end=real.end_to_end,
        per_layer=real.per_layer)


@pytest.mark.parametrize("kind,trace", [("closed", False), ("open", True)])
def test_sound_run_is_correct(kind, trace):
    cell = _cell(kind)
    out = harness.run(cell, 2 ** 31 + 11, 2.0, trace, allow_cpu=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = cell.per_layer if trace else cell.end_to_end
    names = set(out["metrics"])
    assert names <= {m["name"] for m in want}
    if not trace:
        assert {"output_tok_s", "setup_s"} <= names
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving.request import Sequence
    vocab = 512

    def on_token(self, token, now):
        if self.first_token_time is None:
            self.first_token_time = now
        self.generated.append((int(token) + 1) % vocab)

    monkeypatch.setattr(Sequence, "on_token", on_token)
    out = harness.run(_cell("closed"), 5, 2.0, False, allow_cpu=True)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("seed", [9, 10, 2 ** 31 + 3])
def test_control_is_not_correct(seed):
    """The control, the reference computed in fp8, read on the program's own
    prompts and served tokens, fails the limits the program passes."""
    out = harness.run(_cell("closed"), seed, 2.0, False, allow_cpu=True,
                      control="fp8")
    r = out["readings"]
    assert out["correct"]
    assert not harness.judge(r["control"], TINY_LIMITS)[0]
