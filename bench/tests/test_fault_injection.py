"""A run with the timed path broken underneath comes out not correct,
for each fault a one-chip cell can have. The harness's look for a chip
is skipped; everything else is a whole run at the tiny size."""
import pytest

from repro.launch import serve
from tinycell import run_tiny, tiny_cell


def unchanged_state(monkeypatch):
    """The update commits the labelling it was given."""
    orig = serve.batchhl_update

    def frozen(g, batch, lab, **kwargs):
        g2, _, aff = orig(g, batch, lab, **kwargs)
        return g2, lab, aff
    monkeypatch.setattr(serve, "batchhl_update", frozen)
    return "wrong_label_entries"


def half_batch(monkeypatch):
    """Half of each update batch is left out before it reaches the
    device."""
    orig = serve.make_batch
    monkeypatch.setattr(serve, "make_batch", lambda ups, pad_to=None: orig(
        ups[:len(ups) // 2], pad_to=pad_to))
    return "wrong_label_entries"


def altered_answer(monkeypatch):
    """One answer of every microbatch is off by one where it is made."""
    orig = serve.ServeLoop._answer
    monkeypatch.setattr(serve.ServeLoop, "_answer",
                        lambda self, *a: orig(self, *a).at[0].add(1))
    return "wrong_answers"


def stale_reads(monkeypatch):
    """Reads served a version behind under the fresh guarantee."""
    return "max_staleness"


FAULTS = {"unchanged_state": (unchanged_state, {}),
          "half_batch": (half_batch, {}),
          "altered_answer": (altered_answer, {}),
          "stale_reads": (stale_reads, {"serving": {"pipeline": True}})}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(no_disk_cache, monkeypatch, fault):
    plant, config = FAULTS[fault]
    check = plant(monkeypatch)
    res = run_tiny(tiny_cell(**config))
    assert not res["correct"]
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]
