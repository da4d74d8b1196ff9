"""PR 32's harness repair, on the CPU at the tiny size: a configuration of N
volumes in one collection sealed by one `ec.encode -collection` (a test-only
spec: no cell of `BENCHMARK.json` has more than one volume yet), the read
loop's comparison, payloads of mixed needle sizes, and the check that the
deployment after it (`ec4x1g-1m`, cell `ec4x1g.seal`, PR 33) was data alone:
`BENCHMARK.json` lists what the dry check foresaw. Since PR 37 the seal loop
keeps of the early seal four parity files of one volume and of the last all.
"""

import copy
import glob
import json
import os
import statistics
import time

import numpy as np
import pytest

from benchlib import cellrun, cluster, loops, reference, volume, xplane
from conftest import BENCH, ROOT

DATA = os.path.join(BENCH, "tests", "data")
TWO = {"name": "two-volumes", "source": "test only",
       "file": "benchmark/tests/data/two-volumes.json", "reduced": [], "why": "test only"}
TWO_SEAL = {"name": "two.seal", "config": "two-volumes", "traffic": "seal-collection",
            "chips": 1, "why": "test only"}
# what PR 33 (`model_config`) was to add to BENCHMARK.json, and nothing else but
# the configuration's file (tests/data/ec4x1g-1m.json, now configs/ec4x1g-1m.json)
EC4 = {"name": "ec4x1g-1m",
       "source": "BASELINE.json config 5 (multi-volume ec.encode: 256 x 30GB volumes, pmap"
                 " across v5p-8 pod), upstream weed/shell/command_ec_encode.go -collection form",
       "file": "benchmark/tests/data/ec4x1g-1m.json",
       "reduced": ["volume_bytes", "volumes", "chips"],
       "why": "four full 1 GiB volumes of one collection sealed by one verb on one host of"
              " four chips: what the seal does with more than one device"}
EC4_SEAL = {"name": "ec4x1g.seal", "config": "ec4x1g-1m", "traffic": "seal-collection",
            "chips": 4,
            "why": "one closed-loop sealer: ec.encode -collection of four 1 GiB volumes, restored"
                   " in place between verbs; four chips because the volumes are sealed side by"
                   " side, one pipeline a device"}


def spec_with(config: dict, cell: dict) -> dict:
    """`BENCHMARK.json` with a test's configuration and cell in place of any
    of the same name, the cell reporting what `ec1g.seal` reports."""
    spec = copy.deepcopy(cellrun.load_spec())
    spec["configs"] = [c for c in spec["configs"] if c["name"] != config["name"]] + [config]
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] != cell["name"]] + [cell]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "ec1g.seal" in m.get("workloads", []) and cell["name"] not in m["workloads"]:
            m["workloads"].append(cell["name"])
    return spec


def rehearse(spec: dict, cell: str, seed: int = 77, seconds: float = 2.0,
             trace: bool = False, fault: str = "") -> tuple[cellrun.Run, dict]:
    run = cellrun.Run(spec, cell, seed, seconds, trace, "tiny", time.monotonic(),
                      fault=fault, need_chip=False)
    return run, run.execute()


# --- N volumes of one collection, one verb -----------------------------------------
@pytest.fixture(scope="module")
def two_volumes():
    """One sound rehearsal of the two-volume spec, with what was compared."""
    compared = []
    differing = reference.files_differing

    def keep(files, threads=8):
        compared.append([path for path, _ in files])
        return differing(files, threads)

    reference.files_differing = keep
    try:
        # a traced run goes on until it has its second verb; seed 78: of seal
        # 0 four parity files are kept besides the last, so both are compared
        run, result = rehearse(spec_with(TWO, TWO_SEAL), "two.seal", seed=78,
                               seconds=0.5, trace=True)
    finally:
        reference.files_differing = differing
    return run, result, compared


def test_two_volumes_are_sealed_by_one_verb_and_the_run_is_correct(two_volumes):
    run, result, _ = two_volumes
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] == 2 and result["failed"] == 0
    assert result["checks"]["seals_compared"]["value"] == 2
    assert result["checks"]["sample_reads_wrong"] == {"value": 0, "limit": 0}
    # a seal mix's set-up ends settled: the fill, then the kept files, flushed
    assert result["notes"]["sync_s"] >= 0 and result["notes"]["sync_before_window_s"] >= 0
    # the seal cell's per-layer metrics read the same counters over N volumes
    assert {"verb_client_s.seal", "pipeline_stage_busy_share.seal",
            "compiles_in_window.seal"} <= set(result["metrics"])
    assert result["device"]["busy_s_per_chip"] == []  # no device plane on the CPU


def test_the_collection_holds_exactly_the_filled_volumes(two_volumes):
    _, result, _ = two_volumes
    made = result["notes"]["volumes"]
    assert made["collection"] == "warm" and len(made["ids"]) == 2
    assert "/dir/assign?collection=warm" in made["made"]
    # the master grows seven at a time: the five it grew beside them went
    assert len(made["empty_ones_deleted"]) == 5
    assert not set(made["empty_ones_deleted"]) & set(made["ids"])


def test_the_early_seal_keeps_four_parity_files_of_one_volume_and_the_last_all(two_volumes):
    run, result, compared = two_volumes
    (files,) = compared
    assert len(files) == 4 + 2 * 14 and len(set(files)) == len(files)
    early = [p for p in files if os.path.basename(os.path.dirname(p)) == "seal_0"]
    last = [p for p in files if os.path.basename(os.path.dirname(p)) == "seal_1"]
    # one volume, drawn from the seed: every name has that volume's prefix
    prefix = "v1." if run.loop.early_volume else ""
    assert {os.path.basename(p) for p in early} == {
        f"{prefix}ec{s:02d}" for s in (10, 11, 12, 13)}
    assert {os.path.basename(p) for p in last} == (
        {f"ec{s:02d}" for s in range(14)} | {f"v1.ec{s:02d}" for s in range(14)})
    assert result["notes"]["kept_seal_files"] == [4, 28]
    shard = reference.shard_file_size(run.vols[run.loop.early_volume].dat_bytes)
    assert result["notes"]["kept_seal_bytes"][0] == 4 * shard
    assert result["notes"]["kept_seal_bytes"][1] == sum(
        14 * reference.shard_file_size(vol.dat_bytes) for vol in run.vols)


def test_the_early_volume_is_drawn_from_the_seed_and_covers_every_volume():
    class Seeded:
        def __init__(self, seed):
            self.seed, self.vols = seed, [volume.Vol(v, None) for v in range(4)]
            self.dat_bytes, self.server, self.traffic = 0, None, {}

    drawn = [loops.SealLoop(Seeded(seed)) for seed in range(2**31, 2**31 + 64)]
    assert {loop.early_volume for loop in drawn} == {0, 1, 2, 3}
    assert {loop.early for loop in drawn} == {0, 1, 2}
    again = loops.SealLoop(Seeded(2**31 + 5))
    assert (again.early, again.early_volume) == (drawn[5].early, drawn[5].early_volume)


def test_a_verb_counts_both_volumes_bytes(two_volumes):
    run, result, _ = two_volumes
    sizes = [vol.dat_bytes for vol in run.vols]
    assert len(sizes) == 2 and min(sizes) > 45 * 262144
    assert run.loop.unit_bytes == sum(sizes) == run.dat_bytes
    assert run.loop.device_bytes_expected() == result["attempted"] * sum(sizes)
    e2e = run.loop.end_to_end()
    assert e2e["verbs"] == 2
    assert e2e["bytes_per_s_1e9"] == 2 * sum(sizes) / e2e["window_seconds"] / 1e9


def test_each_volume_has_a_payload_of_its_own_and_sample_reads_span_them(two_volumes):
    run, _, _ = two_volumes
    a, b = (vol.payload for vol in run.vols)
    assert bytes(a.of(0)[:64]) != bytes(b.of(0)[:64])
    assert bytes(b.of(0)[:64]) == bytes(volume.Payload(78, 45, 262144, volume=1).of(0)[:64])
    n = run.size["needles"]
    fid, want = run.needle(n + 3)  # needle 3 of the second volume
    assert fid == run.vols[1].fid_of(3) and want == b.of(3)
    rng = np.random.Generator(np.random.SFC64([78, 4]))
    picks = rng.choice(2 * n, size=16, replace=False)
    assert {int(g) // n for g in picks} == {0, 1}


def test_a_byte_flipped_in_the_second_volumes_shard_is_not_correct():
    run, result = rehearse(spec_with(TWO, TWO_SEAL), "two.seal", fault="flip-shard-byte")
    assert os.path.basename(run.loop.produced_shard_path()) == "v1.ec11"
    assert result["correct"] is False
    assert result["checks"]["shard_files_differing"] == {"value": 1, "limit": 0}


def test_a_byte_flipped_in_a_parity_file_of_the_early_seal_is_not_correct():
    # seed 78: seal 0 is the early one; a traced window has two verbs or more
    run, result = rehearse(spec_with(TWO, TWO_SEAL), "two.seal", seed=78,
                           seconds=0.5, trace=True, fault="flip-early-parity-byte")
    path = run.loop.early_parity_path()
    assert os.path.basename(os.path.dirname(path)) == "seal_0"
    assert os.path.basename(path) == ("v1." if run.loop.early_volume else "") + "ec11"
    assert result["notes"]["kept_seal_files"][0] == 4
    assert result["correct"] is False
    assert result["checks"]["shard_files_differing"] == {"value": 1, "limit": 0}
    assert result["checks"]["seals_compared"]["value"] == 2


def test_a_window_that_ends_before_the_early_seal_compares_the_last_alone():
    # seed 77: the early seal would be the third; a traced window of two verbs
    run, result = rehearse(spec_with(TWO, TWO_SEAL), "two.seal", seed=77,
                           seconds=0.1, trace=True)
    assert result["attempted"] == 2 and result["correct"] is True, result["checks"]
    assert result["checks"]["seals_compared"]["value"] == 1
    assert result["notes"]["kept_seal_files"] == [28]
    with pytest.raises(cluster.RunError, match="no early seal"):
        run.loop.early_parity_path()


def test_a_verb_that_does_not_name_one_volume_is_a_failed_operation(monkeypatch):
    shell = cluster.Server.shell

    def one_line_short(self, script, log_path, timeout=900.0):
        rc, text, seconds = shell(self, script, log_path, timeout)
        if os.path.basename(log_path) == "verb_0.log":
            lines = text.splitlines(True)
            spread = [ln for ln in lines if ": shards spread" in ln]
            assert len(spread) == 2
            text = "".join(ln for ln in lines if ln != spread[1])
        return rc, text, seconds

    monkeypatch.setattr(cluster.Server, "shell", one_line_short)
    _, result = rehearse(spec_with(TWO, TWO_SEAL), "two.seal")
    assert result["attempted"] == 1 and result["failed"] == 1
    assert result["correct"] is False
    assert result["checks"]["operations_failed"] == {"value": 1, "limit": 0}


def test_a_verb_that_seals_a_volume_too_many_is_a_failed_operation(monkeypatch):
    run = cellrun.Run(spec_with(TWO, TWO_SEAL), "two.seal", 1, 1.0, False, "tiny", 0.0)
    run.vols = [volume.Vol(v, None) for v in range(2)]
    run.vols[0].vid, run.vols[1].vid = 3, 4
    said = ["ec.encode volume 3: shards spread x", "ec.encode volume 4: shards spread x"]

    class Shell:
        def shell(self, script, log_path):
            assert script == "lock\nec.encode -collection warm\nunlock\n"
            return 0, "\n".join(said) + "\n", 1.0

    run.server = Shell()
    assert run.seal_verb(run.traffic["verb"], "v.log")[0] is True
    said.append("ec.encode volume 14: shards spread x")
    assert run.seal_verb(run.traffic["verb"], "v.log")[0] is False
    del said[1:]
    assert run.seal_verb(run.traffic["verb"], "v.log")[0] is False


@pytest.mark.parametrize("kind", ["repair", "degraded-read"])
def test_the_other_loops_refuse_more_than_one_volume(kind):
    cell = {**TWO_SEAL, "name": "two." + kind, "traffic": kind}
    run = cellrun.Run(spec_with(TWO, cell), cell["name"], 1, 1.0, False, "tiny", 0.0)
    run.vols = [volume.Vol(v, None) for v in range(2)]
    with pytest.raises(cluster.RunError, match="one volume"):
        loops.KINDS[run.traffic["loop"]](run)


# --- the dry check: the deployment after PR 32 was data alone --------------------------
def test_ec4x1g_seal_is_two_entries_and_a_file():
    spec = spec_with(EC4, EC4_SEAL)
    run = cellrun.Run(spec, "ec4x1g.seal", 5, 20.0, False, "real", 0.0)
    assert (run.cell["chips"], run.collection, run.config["volumes"]) == (4, "warm", 4)
    assert run.size == {"needles": 1024, "needle_bytes": 1048576}
    assert run.traffic["verb"].format(vid=0, collection=run.collection) == (
        "lock\nec.encode -collection warm\nunlock\n")
    # PR 33 added exactly what the dry check foresaw: the configuration's entry
    # but for where its file lies, and the cell's but for its `why`
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = json.load(f)
    config_entry = next(c for c in listed["configs"] if c["name"] == "ec4x1g-1m")
    assert config_entry == {**EC4, "file": "benchmark/configs/ec4x1g-1m.json"}
    cell = next(w for w in listed["workloads"] if w["name"] == "ec4x1g.seal")
    assert {**cell, "why": ""} == {**EC4_SEAL, "why": ""}
    for entry in (EC4, EC4_SEAL, config_entry, cell):
        assert all(len(str(v)) <= 200 for v in entry.values())
    with open(os.path.join(DATA, "ec4x1g-1m.json")) as f:
        config = json.load(f)
    assert config["source"] == EC4["source"] and config["reduced"] == EC4["reduced"]


def test_ec4x1g_seal_rehearses_at_the_tiny_size():
    run, result = rehearse(spec_with(EC4, EC4_SEAL), "ec4x1g.seal", seed=2**31 + 32)
    assert result["correct"] is True, result["checks"]
    assert len(result["notes"]["volumes"]["ids"]) == 4 and result["failed"] == 0
    assert len(run.loop.kept) >= 1
    assert run.loop.unit_bytes == sum(vol.dat_bytes for vol in run.vols)


# --- the configuration of one volume is what it was -------------------------------------
def test_the_scalar_payload_is_byte_identical_to_the_parents():
    """Volume 0, `needle_bytes` a number: the stream `[seed, 1]` as before PR
    32 (first and last 64 bytes as the parent's `Payload` made them)."""
    p = volume.Payload(2**31 + 12345, 45, 262144)
    assert bytes(p.of(0)[:64]).hex() == (
        "feda83575bd444127597cc42bb738562b74fb85c3ac648e2a86e8dd429cd2b0c"
        "491af67407923f649b882b7aaeba2b577f352c513ead7db9595efe62a64a7736")
    assert bytes(p.of(44)[-64:]).hex() == (
        "71dd967dc3b3538e16dcfe1a7c427caf1cef79a419238bde788725ccc5e57491"
        "e69270138496cb8b6abc96b6690a9e3bad9149c5635297775d3ed7dd5a96708d")
    assert all(len(p.of(i)) == 262144 for i in range(45))
    assert bytes(volume.Payload(2**31 + 12345, 45, [262144]).of(44)) == bytes(p.of(44))


def test_without_the_new_keys_the_cell_is_one_volume_of_the_default_collection():
    run = cellrun.Run(cellrun.load_spec(), "ec1g.seal", 1, 1.0, False, "tiny", 0.0)
    assert "volumes" not in run.config and "collection" not in run.config
    assert run.collection == ""
    assert run.traffic["verb"] == loops.SEAL_ONE
    assert volume.file_base("/d", "", 7) == "/d/7"
    assert volume.file_base("/d", "warm", 7) == "/d/warm_7"


# --- needles of mixed sizes --------------------------------------------------------------
def test_a_list_of_needle_sizes_is_cycled_and_the_needles_are_disjoint():
    sizes = [4 << 20, 4 << 20, 2 << 20]
    p = volume.Payload(9, 7, sizes)
    assert [len(p.of(i)) for i in range(7)] == [sizes[i % 3] for i in range(7)]
    whole = np.frombuffer(p._buf, dtype=np.uint8)
    at = 0
    for i in range(7):  # needle i starts where needle i - 1 ended
        assert np.shares_memory(np.frombuffer(p.of(i), dtype=np.uint8),
                                whole[at:at + sizes[i % 3]])
        assert np.frombuffer(p.of(i), dtype=np.uint8).ctypes.data == whole.ctypes.data + at
        at += sizes[i % 3]
    assert at == 3 * (10 << 20) - (6 << 20) == sum(len(p.of(i)) for i in range(7))


def test_mixed_sizes_go_through_the_fill_and_the_control(tmp_path):
    import control

    p = volume.Payload(3, 5, [300, 200, 100])
    size = control.make_volume_file(str(tmp_path / "v.dat"), p)
    assert size == 8 + (300 + 200 + 100 + 300 + 200) + 5 * control.RECORD_OVERHEAD
    raw = (tmp_path / "v.dat").read_bytes()
    at = 8
    for i in range(5):
        assert raw[at + control.DATA_AT:at + control.DATA_AT + len(p.of(i))] == p.of(i)
        at += len(p.of(i)) + control.RECORD_OVERHEAD


# --- the read loop's comparison -----------------------------------------------------------
class OneNeedle:
    """As much of a run as `NeedleReader.get` asks for."""

    def __init__(self, nbytes: int) -> None:
        self.payload = volume.Payload(11, 2, nbytes)
        self.logged = []

    def needle(self, g: int):
        return f"1,{g + 1:x}00000000", self.payload.of(g)

    def log(self, msg: str) -> None:
        self.logged.append(msg)


class Answers:
    """A connection that answers every GET with one body."""

    def __init__(self, body: bytes, status: int = 200) -> None:
        self.body, self.status = body, status

    def request(self, method, path):
        self.asked = (method, path)

    def getresponse(self):
        return self

    def read(self):
        return self.body


def altered(body: bytes, how: str) -> bytes:
    if how == "same":
        return bytes(body)
    if how == "last byte off":
        return body[:-1] + bytes([body[-1] ^ 1])
    if how == "first byte off":
        return bytes([body[0] ^ 0x80]) + body[1:]
    if how == "one byte short":
        return body[:-1]
    return body + b"\0"  # one byte long


@pytest.mark.parametrize("nbytes", [64 << 10, 4 << 20])
@pytest.mark.parametrize("how, verdict", [
    ("same", 0), ("last byte off", 1), ("first byte off", 1),
    ("one byte short", 1), ("one byte long", 1)])
def test_get_compares_every_byte_of_the_body(nbytes, how, verdict):
    run = OneNeedle(nbytes)
    reader = loops.NeedleReader(run)
    conn = Answers(altered(run.payload.of(1).tobytes(), how))
    assert reader.get(conn, 1)[1] == verdict
    assert conn.asked == ("GET", "/1,200000000")
    assert len(reader.compare_seconds) == 1


def test_get_calls_a_failed_read_failed_and_not_wrong():
    run = OneNeedle(1024)
    reader = loops.NeedleReader(run)
    assert reader.get(Answers(run.payload.of(0).tobytes(), status=500), 0)[1] == 2
    assert run.logged and not reader.compare_seconds


def test_the_control_and_the_read_loop_share_one_comparison():
    import inspect

    import control

    assert "volume.same_bytes(" in inspect.getsource(loops.NeedleReader.get)
    assert inspect.getsource(control.control).count("volume.same_bytes(") == 2
    for src in (inspect.getsource(loops.NeedleReader.get), inspect.getsource(control.control)):
        assert "== payload.of" not in src and "!= payload.of" not in src


def test_a_4_mib_comparison_costs_a_memcmp():
    """12 ms as `bytes == memoryview` (item by item, under the lock), which
    capped `warm4m.degraded-read` at 82 reads/s until PR 32."""
    p = volume.Payload(5, 8, 4 << 20)
    bodies = [p.of(i).tobytes() for i in range(8)]
    took = []
    for _ in range(5):
        for i, body in enumerate(bodies):
            t0 = time.perf_counter()
            assert volume.same_bytes(body, p.of(i))
            took.append(time.perf_counter() - t0)
    assert statistics.median(took) < 3e-3


# --- what only this kind of PR may edit -----------------------------------------------------
def test_a_layer_metrics_file_names_no_cells_BENCHMARK_json_does():
    spec = cellrun.load_spec()
    files = {os.path.basename(p)[:-5]: cellrun.load_json(p)
             for p in glob.glob(os.path.join(BENCH, "layer_metrics", "*.json"))}
    assert set(files) == {m["name"] for m in spec["per_layer"]}
    for m in spec["per_layer"]:
        assert "workloads" not in files[m["name"]]
        for key in ("layer", "unit", "better", "source", "moves"):
            assert files[m["name"]][key] == m[key], (m["name"], key)


def test_busy_seconds_of_every_chip_with_an_idle_one_among_them():
    class Ev:
        def __init__(self, start, dur):
            self.start_ns, self.duration_ns, self.name = start, dur, "%k.1 = u8[1]{0} custom-call()"

    class Line:
        name = xplane.OPS_LINE

        def __init__(self, events):
            self.events = events

    class Plane:
        def __init__(self, name, events):
            self.name, self.lines, self.stats = name, [Line(events)], []

    class Profile:
        planes = [Plane("/device:TPU:0", [Ev(0, 10**9), Ev(2 * 10**9, 10**9)]),
                  Plane("/device:TPU:1", []),
                  Plane("/device:TPU:2", [Ev(10**9, 10**9)]),
                  Plane("/device:CUSTOM:Megascale Trace", []),
                  Plane("/host:CPU", [Ev(0, 4 * 10**9)])]

    got = xplane.reduce(Profile(), 0.0, 4.0)
    assert got["busy_s_per_chip"] == [2.0, 0.0, 1.0]
    assert got["busy_s"] == 1.5 and got["chips"] == 2  # the mean over the chips used
