"""A run with the timed path broken underneath has to come out not correct.

Each test drives the rest of a run at a tiny size on the CPU, past the chip
check, with one fault planted in the program: a step that returns its input
unchanged, half of every batch left out, or one answer altered where it is
produced.  (No cell spans chips, so there is no exchange to leave out.)
"""
import dataclasses

import pytest

from bench_tiny import run_tiny, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


def _halve(t):
    from repro.core.table import Table

    return Table(columns=dict(t.columns), n_valid=t.n_valid // 2)


def batch_anonymize_unchanged(mp):
    import repro.challenge.pipeline as pipeline

    real = pipeline.anonymize
    mp.setattr(pipeline, "anonymize", lambda t, key, **kw: dataclasses.replace(
        real(t, key, **kw), table=t))


def batch_half_rows(mp):
    import repro.challenge.pipeline as pipeline

    real = pipeline.analyze
    mp.setattr(pipeline, "analyze", lambda t, **kw: real(_halve(t), **kw))


def batch_answer_altered(mp):
    import repro.challenge.pipeline as pipeline

    real = pipeline.analyze

    def altered(t, **kw):
        r = real(t, **kw)
        return dataclasses.replace(r, scalars=dataclasses.replace(
            r.scalars, max_source_fanout=r.scalars.max_source_fanout + 1))

    mp.setattr(pipeline, "analyze", altered)


def fold_unchanged(mp):
    import repro.stream.engine as engine

    mp.setattr(engine, "_jitted_update", lambda *a: lambda s, *b: s)
    mp.setattr(engine, "_jitted_sketch_update", lambda *a: lambda s, *b: s)


def fold_half_batch(mp):
    import jax

    import repro.stream.engine as engine

    mp.setattr(engine, "_jitted_update", lambda backend, donate: jax.jit(
        lambda s, src, dst, win, n: engine.update_state(
            s, src, dst, win, n // 2, backend=backend)))
    mp.setattr(engine, "_jitted_sketch_update", lambda backend, d: jax.jit(
        lambda s, src, dst, n: engine.update_sketch(s, src, dst, n // 2,
                                                    backend=backend)))


def snapshot_answer_altered(mp):
    import repro.stream.engine as engine

    real_snap, real_sketch = engine._jitted_snapshot, engine.snapshot_sketch

    def exact(top_k, backend):
        fn = real_snap(top_k, backend)

        def altered(state):
            r = fn(state)
            return dataclasses.replace(r, scalars=dataclasses.replace(
                r.scalars, unique_links=r.scalars.unique_links + 1))
        return altered

    mp.setattr(engine, "_jitted_snapshot", exact)
    mp.setattr(engine, "snapshot_sketch", lambda *a, **k: dataclasses.replace(
        real_sketch(*a, **k), unique_sources=2 * real_sketch(
            *a, **k).unique_sources))


@pytest.mark.parametrize("cell,fault", [
    ("batch-rmat22", batch_anonymize_unchanged),
    ("batch-rmat22", batch_half_rows),
    ("batch-rmat22", batch_answer_altered),
    ("service-exact-rmat20", fold_unchanged),
    ("service-exact-rmat20", fold_half_batch),
    ("service-exact-rmat20", snapshot_answer_altered),
    ("service-sketch-rmat20", fold_unchanged),
    ("service-sketch-rmat20", fold_half_batch),
    ("service-sketch-rmat20", snapshot_answer_altered),
], ids=lambda x: x if isinstance(x, str) else x.__name__)
def test_planted_fault_is_not_correct(root, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = run_tiny(root, cell, seconds=0.2)
    assert line["correct"] is False
    assert line["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
