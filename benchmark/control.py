"""The control of each cell's comparison: the plain reference put in the
program's place with one guarantee of the configuration broken. It has to come
out as not correct by the very comparison a run makes.

    python benchmark/control.py --workload <name> --seed <n> [--size tiny]

seal    all 14 shard files written from another MDS code of the same shape
        (a Cauchy matrix): any ten still rebuild the rest, none is
        byte-identical to upstream's
repair  the lost shard rebuilt with that code's decode rows from sound shards
read    degraded reads served without reconstruction: the lost shard's bytes
        come back as zeros

It needs no server and no chip: the volume file is made from the seed at the
cell's size. Prints one JSON line; exit code 0 when the control failed the
comparison as it must, 1 when it passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import cellrun, cluster, reference, volume  # noqa: E402

RECORD_OVERHEAD = 40  # header, checksum, timestamp and padding of a v3 needle
DATA_AT = 20          # where a record's payload starts


def make_volume_file(path: str, payload: volume.Payload) -> int:
    """A volume file of the cell's size and layout: an 8-byte superblock,
    then one record per needle with the payload inside."""
    with open(path, "wb") as f:
        f.write(bytes(8))
        for i in range(payload.needles):
            f.write(bytes(DATA_AT))
            f.write(payload.of(i))
            f.write(bytes(RECORD_OVERHEAD - DATA_AT))
    return os.path.getsize(path)


def control(workload: str, seed: int, size: str, workdir: str) -> dict:
    spec = cellrun.load_spec()
    run = cellrun.Run(spec, workload, seed, 0.0, False, size, 0.0)
    kind = run.traffic["loop"]
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    try:
        dat = os.path.join(workdir, "control.dat")
        sound = reference.coding_matrix()
        other = reference.cauchy_matrix()
        path_of = lambda s: os.path.join(workdir, f"control.ec{s:02d}")  # noqa: E731
        differing = sound_differing = 0
        # every volume of the configuration in turn; a mix that reads has one
        for v in range(int(run.config.get("volumes", 1))):
            payload = volume.Payload(seed, run.size["needles"],
                                     run.size["needle_bytes"], v)
            dat_bytes = make_volume_file(dat, payload)
            if kind not in ("seal", "repair"):
                break
            want = reference.expected_shards(dat)
            if kind == "seal":
                made = reference.expected_shards(dat, matrix=other)
            else:
                lost = int(np.random.Generator(
                    np.random.SFC64([seed, 2])).permutation(reference.TOTAL)[0])
                present = [s for s in range(reference.TOTAL) if s != lost]
                rows = reference.decode_rows(other, present, [lost])
                made = want.copy()
                made[lost] = reference.apply_matrix(rows, want[sorted(present)[:10]])[0]
            for s in range(reference.TOTAL):
                made[s].tofile(path_of(s))
            files = [(path_of(s), want[s]) for s in range(reference.TOTAL)]
            differing += reference.files_differing(files)
            for s in range(reference.TOTAL):
                want[s].tofile(path_of(s))
            sound_differing += reference.files_differing(files)
        if kind in ("seal", "repair"):
            checks = {"shard_files_differing": (differing, 0)}
            sound_checks = {"shard_files_differing": (sound_differing, 0)}
        elif kind == "read":
            lost = [int(s) for s in run.config["lost_shards"]]
            index, at = [], 8
            for i in range(payload.needles):
                index.append((i, at, len(payload.of(i))))
                at += index[-1][2] + RECORD_OVERHEAD
            touching = volume.records_on_shards(index, dat_bytes, lost)
            rng = np.random.Generator(np.random.SFC64([seed, 3, 0]))
            picks = [sorted(touching)[int(j)] for j in
                     rng.integers(0, len(touching), size=min(256, len(touching)))]
            block = reference.SMALL_BLOCK
            wrong = sound_wrong = 0
            rows = reference.padded_rows(dat)
            for i in picks:
                off, nb = index[i][1] + DATA_AT, index[i][2]
                body = np.frombuffer(payload.of(i), dtype=np.uint8).copy()
                rebuilt = body.copy()
                for b in range(off // block, (off + nb - 1) // block + 1):
                    if b % reference.DATA not in lost:
                        continue
                    lo, hi = max(off, b * block), min(off + nb, (b + 1) * block)
                    body[lo - off:hi - off] = 0  # the control: no reconstruction
                    # the sound path: the interval rebuilt from ten others
                    r, s = divmod(b, reference.DATA)
                    cut = slice(lo - b * block, hi - b * block)
                    data = rows[r][:, cut]
                    shards = np.concatenate(
                        [data, reference.apply_matrix(sound[reference.DATA:], data)])
                    present = [x for x in range(reference.TOTAL) if x not in lost]
                    dec = reference.decode_rows(sound, present, [s])
                    rebuilt[lo - off:hi - off] = reference.apply_matrix(
                        dec, shards[sorted(present)[:10]])[0]
                # by the read loop's own comparison (NeedleReader.get)
                wrong += not volume.same_bytes(bytes(body), payload.of(i))
                sound_wrong += not volume.same_bytes(bytes(rebuilt), payload.of(i))
            checks = {"reads_wrong": (wrong, 0)}
            sound_checks = {"reads_wrong": (sound_wrong, 0)}
        else:
            raise cluster.RunError(f"no control for loop kind {kind!r}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return {
        "workload": workload, "seed": seed, "size": size,
        "control_correct": cellrun.Run.is_correct(checks),
        "control": {k: {"value": int(v), "limit": lim} for k, (v, lim) in checks.items()},
        "reference_in_place_correct": cellrun.Run.is_correct(sound_checks),
    }


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--size", choices=("real", "tiny"), default="real")
    opts = p.parse_args(argv)
    out = control(opts.workload, opts.seed, opts.size,
                  os.path.join(cluster.ROOT, ".bench_work", "control_" + opts.workload))
    print(json.dumps(out), flush=True)
    return 0 if not out["control_correct"] and out["reference_in_place_correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
