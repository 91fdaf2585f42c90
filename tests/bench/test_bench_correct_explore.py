"""`correct` of an explore cell (resnet18 on MC:Hetero, pop 16, three
generations) on the CPU: true for the program as it is, false with the
timed path broken underneath or with a control in the program's place."""
import numpy as np
import pytest

import benchtools


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtools.tiny_root(tmp_path_factory.mktemp("bench"))


def _limits(root):
    from bench import cells
    return cells.resolve("tiny.explore", root).config["limits"]


def test_sound_run_is_correct(root, monkeypatch):
    benchtools.serialize_on_host(monkeypatch)
    res = benchtools.run_cell(root, "tiny.explore")
    assert res["correct"], res
    assert set(res["checks"]) == {"exact_gap", "fitness_gap"}
    assert res["metrics"]["explore_points_per_s"]["value"] > 0


def _exact_altered(monkeypatch):
    """The exact scheduler's latency comes out 0.1% long."""
    from repro.core.scheduler import ScheduleEngine
    real = ScheduleEngine.schedule

    def altered(self, *a, **kw):
        res = real(self, *a, **kw)
        res.latency_cc *= 1.001
        return res
    monkeypatch.setattr(ScheduleEngine, "schedule", altered)


def _scores_altered(monkeypatch):
    """The batched fitness's energies come out 1% high."""
    from repro.core.vectorized import BatchedFitness
    real = BatchedFitness.scores

    def altered(self, genomes):
        out = real(self, genomes).copy()
        out[:, 1] *= 1.01
        return out
    monkeypatch.setattr(BatchedFitness, "scores", altered)


def _half_batch(monkeypatch):
    """Only the first half of each population is scored; the second half
    is given the first half's scores."""
    from repro.core.vectorized import BatchedFitness
    real = BatchedFitness.scores

    def half(self, genomes):
        g = np.asarray(genomes)
        out = real(self, g[:(len(g) + 1) // 2])
        return np.concatenate([out, out])[:len(g)]
    monkeypatch.setattr(BatchedFitness, "scores", half)


def _queue_state_unchanged(monkeypatch):
    """Each wavefront step's FCFS serialization hands back the resources'
    free times it was given."""
    from repro.kernels import ref
    real = ref.serialize_prefix_ref

    def stale(free0, release, dur):
        fin, _ = real(free0, release, dur)
        return fin, free0
    monkeypatch.setattr(ref, "serialize_prefix_ref", stale)


@pytest.mark.parametrize("fault", [_exact_altered, _scores_altered,
                                   _half_batch, _queue_state_unchanged])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    benchtools.serialize_on_host(monkeypatch)
    fault(monkeypatch)
    res = benchtools.run_cell(root, "tiny.explore", seed=6_000_000_013)
    assert not res["correct"], res
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("control", ["fitness", "exact"])
def test_controls_fail_the_limits(root, monkeypatch, control):
    """A whole run with the control in the program's place, as
    `bench/calibrate.py --control` makes it on the chip: bfloat16 plain
    fitness in place of the float32 scores, or approximate scores reported
    in place of exact ones. `correct` reads false."""
    benchtools.serialize_on_host(monkeypatch)
    res = benchtools.run_cell(root, "tiny.explore", seed=6_000_000_029,
                              control=control)
    assert not res["correct"], res
    name = {"fitness": "fitness_gap", "exact": "exact_gap"}[control]
    assert res["checks"][name]["value"] > res["checks"][name]["limit"]
