"""The harness's check catches a broken timed path.

Each test skips only the harness's look for a chip: it drives a whole run
of the cell (set-up, window, reference check) on the CPU at 32 px, with
the program broken underneath, and sees ``correct`` come out false. The
same run unbroken comes out true. One chip and no training, so the
faults the cell can have are an answer altered where it is produced and
half of each batch left out.
"""
import numpy as np
import pytest

from bench import harness
from bench.tests.tiny import tiny_catalog

SEED = 2**33 + 17
CELL = "ingest-distinct"


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    return harness.Catalog(tiny_catalog(tmp_path_factory.mktemp("tiny")))


def run(catalog):
    return harness.run_cell(catalog, CELL, SEED, 0.3, False)


def caught(out):
    gap = out["checks"]["gap_max"]
    return (not out["correct"] and out["failed"] > 0
            and gap["value"] > gap["limit"])


def test_sound_run_is_correct(catalog):
    out = run(catalog)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("where", ["score", "label"])
def test_altered_answer_is_caught(catalog, monkeypatch, where):
    """One score moved by 1e-3 as the device returns it, or one decided
    label flipped as it is graded."""
    from repro.engine.ingest import IngestPipeline

    if where == "score":
        whole = IngestPipeline._score_refs

        def altered(self, frames):
            out = whole(self, frames)
            out[0, 1] += 1e-3
            return out
        monkeypatch.setattr(IngestPipeline, "_score_refs", altered)
    else:
        grade = IngestPipeline._grade

        def altered(self, casc, s0):
            lab, decided, margin = grade(self, casc, s0)
            return 1 - lab, decided, margin
        monkeypatch.setattr(IngestPipeline, "_grade", altered)
    assert caught(run(catalog))


def test_half_of_each_batch_left_out_is_caught(catalog, monkeypatch):
    from repro.engine.ingest import IngestPipeline

    whole = IngestPipeline.ingest

    def half(self, frames, ids):
        keep = len(ids) // 2
        return whole(self, frames[:keep], np.asarray(ids)[:keep])
    monkeypatch.setattr(IngestPipeline, "ingest", half)
    assert caught(run(catalog))
