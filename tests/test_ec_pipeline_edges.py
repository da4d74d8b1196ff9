"""An EC verb's edges, timed where they happen.

`encoder._Edges` times four edges of every encode or rebuild pipeline's call
under `SeaweedFS_volume_ec_pipeline_edge_seconds{verb, edge}`, each a
`trace.phase` of its own name: `setup` (the call's entry to the pipeline's),
`head` (to the return of the first `encode_job`), `tail` (from the return of
the last one to the writer's end) and `finish` (the pipeline's return to the
call's). Each stage thread adds its CPU seconds to
`SeaweedFS_volume_ec_pipeline_cpu_seconds_total{stage}` once, at its end.
Held here on the numpy backend over volumes of many batches: (a) each edge
once a pipeline under its verb, and none for a call that runs no pipeline or
raises; (b) `setup` + the pipeline's wall + `finish` is the call; (c) a slow
first program lies in `head`, writes after the last enqueue in `tail`; (d) a
spinning writer's CPU is counted under `write` and a sleeping one's is not;
(e) the annotations open and close on their threads, and cross only the two
that `setup` and `finish` must hold an end of.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

import pytest

from seaweedfs_tpu.ops.rs_kernel import RSCodec
from seaweedfs_tpu.stats import trace
from seaweedfs_tpu.storage.erasure_coding import encoder
from tests.test_ec_batch_buffers import (
    DATA,
    LARGE,
    LOST,
    SMALL,
    counted,
    encode,
    ext,
    oracle_shards,
    seeded,
    shard_files,
)
from tests.test_trace_phases import _grown

EDGE = trace.EC_PIPELINE_EDGE_SECONDS
CPU = trace.EC_PIPELINE_CPU_SECONDS
EDGES = trace.EC_PIPELINE_EDGES
# two large rows in column slices and 41 small rows: 13 batches to encode;
# 11 to rebuild
BATCH = 1000
DAT = LARGE * DATA * 2 + SMALL * DATA * 40 + 33
VERBS = {"seal": "encode", "rebuild": "rebuild"}


class Boom(RuntimeError):
    pass


class Pipelines:
    """Every `_run_pipeline` call of a test, with its wall seconds, and hooks
    into its stages: `on_encode(batch)` before each `encode_job`,
    `on_write(batch, batches)` after each `write_job`."""

    def __init__(self) -> None:
        self.walls: list[float] = []
        self.batches: list[int] = []
        self.on_encode = None
        self.on_write = None


@pytest.fixture()
def pipelines(monkeypatch) -> Pipelines:
    seen = Pipelines()
    real = encoder._run_pipeline

    def run(jobs, read_job, encode_job, write_job, *args, **kwargs):
        jobs = list(jobs)
        index = {job: i for i, job in enumerate(jobs)}
        seen.batches.append(len(jobs))

        def encode_hooked(job, buf):
            if seen.on_encode is not None:
                seen.on_encode(index[job])
            return encode_job(job, buf)

        def write_hooked(job, buf, handle):
            write_job(job, buf, handle)
            if seen.on_write is not None:
                seen.on_write(index[job], len(jobs))

        t0 = time.perf_counter()
        try:
            return real(jobs, read_job, encode_hooked, write_hooked, *args, **kwargs)
        finally:
            seen.walls.append(time.perf_counter() - t0)

    monkeypatch.setattr(encoder, "_run_pipeline", run)
    return seen


@pytest.fixture()
def volume(tmp_path) -> tuple[str, list[bytes]]:
    """A sealed volume (numpy oracle) and its fourteen shard files."""
    dat = seeded(DAT, 391)
    return encode(tmp_path / "v", dat, RSCodec(backend="numpy")), oracle_shards(dat)


def run_verb(verb: str, base: str) -> float:
    """One verb through the pipeline, its call's seconds."""
    codec = RSCodec(backend="numpy")
    if verb == "rebuild":
        for i in LOST:
            os.unlink(base + ext(i))
    t0 = time.perf_counter()
    if verb == "seal":
        encoder.write_ec_files(base, codec=codec, large_block_size=LARGE,
                               small_block_size=SMALL, batch=BATCH)
    else:
        assert sorted(encoder.rebuild_ec_files(base, codec=codec, chunk=BATCH)) == list(LOST)
    return time.perf_counter() - t0


def fail_last(batch: int, batches: int) -> None:
    if batch == batches - 1:
        raise Boom("the last batch's write")


def edges_grown(before: dict, after: dict, what: str) -> dict:
    """{(verb, edge): growth of `_sum` or `_count`}."""
    return {(v, e): _grown(before, after, EDGE + what, verb=v, edge=e)
            for v in trace.EC_PIPELINE_VERBS for e in EDGES}


# --- (a) once a pipeline, under its verb ------------------------------------------------
@pytest.mark.parametrize("verb", ["seal", "rebuild"])
def test_each_edge_once_a_pipeline_under_its_verb(volume, pipelines, verb):
    base, want = volume
    before = counted()
    run_verb(verb, base)
    after = counted()
    assert pipelines.batches[0] >= 3
    counts = edges_grown(before, after, "_count")
    assert counts == {(v, e): float(v == VERBS[verb])
                      for v in trace.EC_PIPELINE_VERBS for e in EDGES}
    assert all(s > 0 for (v, _), s in edges_grown(before, after, "_sum").items()
               if v == VERBS[verb])
    assert shard_files(base) == want


def test_a_call_that_runs_no_pipeline_counts_no_edge(volume, pipelines):
    base, _ = volume
    before = counted()
    assert encoder.rebuild_ec_files(base, codec=RSCodec(backend="numpy"), chunk=BATCH) == []
    assert pipelines.walls == []
    assert not any(edges_grown(before, counted(), "_count").values())


@pytest.mark.parametrize("verb", ["seal", "rebuild"])
def test_a_pipeline_that_raises_counts_no_edge_it_left_open(volume, pipelines, verb):
    base, _ = volume
    pipelines.on_write = fail_last
    before = counted()
    with pytest.raises(Boom):
        run_verb(verb, base)
    counts = edges_grown(before, counted(), "_count")
    # the head was over before anything failed; the tail and finish never end
    assert {e: counts[(VERBS[verb], e)] for e in EDGES} == {
        "setup": 1.0, "head": 1.0, "tail": 0.0, "finish": 0.0}


# --- (b) setup + the pipeline's wall + finish is the call ------------------------------
@pytest.mark.parametrize("verb", ["seal", "rebuild"])
def test_setup_wall_and_finish_are_the_whole_call(volume, pipelines, verb):
    base, _ = volume
    before = counted()
    call = run_verb(verb, base)
    sums = edges_grown(before, counted(), "_sum")
    v = VERBS[verb]
    (wall,) = pipelines.walls
    assert abs(sums[(v, "setup")] + wall + sums[(v, "finish")] - call) < 0.005
    assert sums[(v, "head")] + sums[(v, "tail")] <= wall


# --- (c) a slow first program lies in the head, writes after the last enqueue in the tail
@pytest.mark.parametrize("verb", ["seal", "rebuild"])
def test_the_head_holds_a_slow_first_program(volume, pipelines, verb):
    base, _ = volume
    pipelines.on_encode = lambda batch: time.sleep(0.2) if batch == 0 else None
    before = counted()
    run_verb(verb, base)
    sums = edges_grown(before, counted(), "_sum")
    assert sums[(VERBS[verb], "head")] >= 0.2
    assert sums[(VERBS[verb], "tail")] < 0.2


@pytest.mark.parametrize("verb", ["seal", "rebuild"])
def test_the_tail_holds_the_writes_after_the_last_enqueue(volume, pipelines, verb):
    base, _ = volume
    pipelines.on_write = (
        lambda batch, batches: time.sleep(0.25) if batch == batches - 1 else None)
    before = counted()
    run_verb(verb, base)
    sums = edges_grown(before, counted(), "_sum")
    assert sums[(VERBS[verb], "tail")] >= 0.25
    assert sums[(VERBS[verb], "head")] < 0.25


# --- (d) each stage thread's CPU, once a pipeline ---------------------------------------
def spin(seconds: float) -> None:
    c0 = time.thread_time()
    while time.thread_time() - c0 < seconds:
        pass


@pytest.mark.parametrize("writer", ["spins", "sleeps"])
def test_the_write_stage_counts_its_threads_cpu(volume, pipelines, writer):
    base, _ = volume
    wait = spin if writer == "spins" else time.sleep
    pipelines.on_write = lambda batch, batches: wait(0.3) if batch == 0 else None
    before = counted()
    run_verb("seal", base)
    after = counted()
    grown = {s: _grown(before, after, CPU, stage=s) for s in trace.EC_PIPELINE_STAGES}
    assert all(g >= 0 for g in grown.values())
    assert {dict(k[1])["stage"] for k in after if k[0] == CPU} == set(
        trace.EC_PIPELINE_STAGES)
    if writer == "spins":
        assert grown["write"] >= 0.15
    else:
        assert grown["write"] < 0.05


# --- (e) the annotations, on their threads ----------------------------------------------
@pytest.fixture()
def annotations(monkeypatch) -> list:
    """`trace._annotation` replaced by a stand-in that logs each enter and
    exit as (thread, "enter"|"exit", annotation, `perf_counter()`)."""
    log: list = []
    lock = threading.Lock()

    class Annotation:
        def __init__(self, name: str) -> None:
            self.name = name

        def __enter__(self):
            with lock:
                log.append((threading.current_thread().name, "enter", self,
                            time.perf_counter()))
            return self

        def __exit__(self, *exc):
            with lock:
                log.append((threading.current_thread().name, "exit", self,
                            time.perf_counter()))

    monkeypatch.setattr(trace, "_annotation", Annotation)
    return log


def crossings(log: list) -> tuple[set, list]:
    """({names of two annotations of one thread of which one was opened
    inside the other and left after it}, [annotations never left])."""
    stacks: dict[str, list] = {}
    crossed = set()
    for thread, what, ann, _ in log:
        stack = stacks.setdefault(thread, [])
        if what == "enter":
            stack.append(ann)
            continue
        assert ann in stack, f"{ann.name} left on a thread that did not enter it"
        i = stack.index(ann)
        crossed |= {frozenset((ann.name, inner.name)) for inner in stack[i + 1:]}
        del stack[i]
    return crossed, [a.name for s in stacks.values() for a in s]


@pytest.mark.parametrize("verb", ["seal", "rebuild", "seal-that-raises"])
def test_the_edges_open_and_close_nested_on_their_threads(
        volume, pipelines, annotations, verb):
    base, _ = volume
    raises = verb.endswith("raises")
    if raises:
        pipelines.on_write = fail_last
    with pytest.raises(Boom) if raises else contextlib.nullcontext():
        run_verb(verb.split("-")[0], base)
    me = threading.current_thread().name
    names = {"ec.pipeline." + e for e in EDGES}
    edges = [(thread, what, ann.name) for thread, what, ann, _ in annotations
             if ann.name in names]
    want = [e for e in EDGES if not raises or e in ("setup", "head", "tail")]
    assert edges == [(me, what, "ec.pipeline." + e)
                     for e in want for what in ("enter", "exit")]
    crossed, left_open = crossings(annotations)
    assert left_open == []
    # setup holds the kernel span's start, finish its end: they cross it,
    # and nothing else crosses anything
    span = "ec.encode" if verb.startswith("seal") else "ec.rebuild"
    assert crossed == {frozenset(("ec.pipeline.setup", span))} | (
        set() if raises else {frozenset(("ec.pipeline.finish", span))})
    # the stage spans of every batch lie on their own threads
    assert {ann.name for thread, _, ann, _ in annotations if thread == "ec-writer"} == {
        "ec.pipeline.write", "ec.pipeline.write.drain", "ec.pipeline.write.pwrite"}


@pytest.mark.parametrize("verb", ["seal", "rebuild"])
def test_each_annotation_lasts_as_long_as_its_edge_counts(
        volume, pipelines, annotations, verb):
    # slow writes fill the write queue: the last enqueue returns long before
    # the reader's end marker can reach the caller, and the tail's
    # annotation still opens there
    base, _ = volume
    pipelines.on_write = lambda batch, batches: time.sleep(0.02)
    before = counted()
    run_verb(verb, base)
    sums = edges_grown(before, counted(), "_sum")
    opened = {}
    lasted = {}
    for _, what, ann, t in annotations:
        if ann.name.startswith("ec.pipeline.") and ann.name[12:] in EDGES:
            if what == "enter":
                opened[ann.name[12:]] = t
            else:
                lasted[ann.name[12:]] = t - opened[ann.name[12:]]
    assert sums[(VERBS[verb], "tail")] > 0.05  # the writes still queued
    for edge in EDGES:
        assert abs(lasted[edge] - sums[(VERBS[verb], edge)]) < 0.01, edge
