"""The admin shell's import closure (PR 29): a `shell` child is an HTTP
client, one process a script, so what it loads before its first RPC is what
an operator waits for. A child started the way the benchmark's runner
starts one (`command.main shell -master ...`, the script on stdin) runs the
EC verbs without numpy, jax, the HTTP server or the codec, prints what the
same script prints in-process, and no module under `seaweedfs_tpu/shell/`
imports the server, the kernels or numpy at its top."""

import ast
import io
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from seaweedfs_tpu.server.httpd import get_json, http_request, post_json
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume import VolumeServer
from seaweedfs_tpu.shell import shell as shell_mod
from seaweedfs_tpu.shell.env import CommandEnv, ServerView
from seaweedfs_tpu.stats import trace

ROOT = pathlib.Path(__file__).resolve().parent.parent

# the runner's child, and on its way out the modules it had loaded
CHILD = """
import json, sys
from seaweedfs_tpu.command.main import main
rc = main(["shell", *sys.argv[1:]])
sys.stderr.write("MODULES " + json.dumps(sorted(sys.modules)) + "\\n")
raise SystemExit(rc)
"""

# what a client of the admin API has no use for
KEPT_OUT = ("numpy", "jax", "http.server", "urllib.request")
OWN_KEPT_OUT = ("seaweedfs_tpu.ops.", "seaweedfs_tpu.server.")

# `help` lists every verb: this many on the parent of PR 29
COMMAND_COUNT = 87


def run_child(master_url: str, script: str) -> tuple[int, str, list[str]]:
    """-> (exit code, what the child printed, its sys.modules at exit)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, "-master", master_url],
        cwd=ROOT, env=env, input=script, text=True, capture_output=True,
        timeout=300,
    )
    modules = []
    for line in proc.stderr.splitlines():
        if line.startswith("MODULES "):
            modules = json.loads(line[len("MODULES "):])
    return proc.returncode, proc.stdout, modules


def run_here(master_url: str, script: str) -> tuple[int, str]:
    out = io.StringIO()
    rc = shell_mod.run_shell(master_url, script=script, out=out)
    return rc, out.getvalue()


@pytest.fixture()
def cluster(tmp_path):
    """master + one volume server, as in the benchmark's verb cells."""
    master = MasterServer(port=0, pulse_seconds=1, volume_size_limit_mb=64)
    master.start()
    vs = VolumeServer([str(tmp_path / "v0")], master.url, port=0,
                      pulse_seconds=1, max_volume_count=10)
    vs.start()
    yield master, vs
    vs.stop()
    master.stop()


def two_filled_volumes(master) -> tuple[int, int]:
    """Blobs until two volumes hold some: one for the child, one for the
    same script in-process."""
    filled: dict[int, int] = {}
    for i in range(400):
        a = get_json(f"{master.url}/dir/assign")
        vid = int(a["fid"].split(",")[0])
        if len(filled) == 2 and vid not in filled:
            continue
        status, _, _ = http_request(
            "POST", f"http://{a['publicUrl']}/{a['fid']}",
            f"blob-{i}-".encode() * 400)
        assert status == 201
        filled[vid] = filled.get(vid, 0) + 1
        if len(filled) == 2 and min(filled.values()) >= 3:
            break
    assert len(filled) == 2, filled
    first, second = sorted(filled)
    return first, second


def drop_shard(vs, vid: int, shard: int) -> None:
    removed = post_json(f"{vs.url}/admin/ec/delete_shards",
                        {"volume": vid, "collection": "", "shards": [shard],
                         "delete_index": False})
    assert removed["removed"] == [shard]
    vs.heartbeat_once()


def prepare_nothing(master, vs):
    return None


def prepare_sealed_and_degraded(master, vs):
    """Both volumes sealed, then shard 3 of each removed."""
    vids = two_filled_volumes(master)
    for vid in vids:
        rc, out = run_here(
            master.url, f"lock; ec.encode -volumeId {vid}; unlock")
        assert rc == 0 and "shards spread" in out, out
        drop_shard(vs, vid, 3)
    return vids


@pytest.mark.parametrize("script, prepare, expect", [
    ("", prepare_nothing, ""),
    ("lock; ec.encode -volumeId {vid}; unlock", lambda m, vs: two_filled_volumes(m),
     "shards spread"),
    ("lock; ec.rebuild -volumeId {vid}; unlock", prepare_sealed_and_degraded,
     "rebuilt shards [3]"),
    ("help", prepare_nothing, "ec.encode"),
], ids=["empty", "ec.encode", "ec.rebuild", "help"])
def test_shell_child(cluster, script, prepare, expect):
    """The child on one volume, the same script in-process on the other."""
    master, vs = cluster
    mine, theirs = prepare(master, vs) or (0, 0)
    rc, printed, modules = run_child(master.url, script.format(vid=mine))
    assert rc == 0, printed
    assert expect in printed and "error" not in printed
    here_rc, here = run_here(master.url, script.format(vid=theirs))
    assert here_rc == 0
    assert printed == here.replace(f"volume {theirs}:", f"volume {mine}:")
    assert "seaweedfs_tpu.shell.commands_ec" in modules
    if script == "help":
        assert len(printed.split()) == COMMAND_COUNT
        return
    loaded = [m for m in modules
              if m.split(".")[0] in KEPT_OUT or m in KEPT_OUT
              or m.startswith(OWN_KEPT_OUT)]
    assert not loaded, loaded
    if "ec.rebuild" in script:
        # one holder: `auto` hands the repair to classic before any
        # coefficient is wanted, so the decoder stayed out too
        assert "(classic)" in printed
        assert "seaweedfs_tpu.storage.erasure_coding.decoder" not in modules


def _holder(id_: str, shards: list[int], free: int = 5) -> ServerView:
    return ServerView("dc", "r", {
        "id": id_, "url": id_, "max_volume_count": free + 1,
        "ec_shard_infos": [{"id": 7, "shards": shards}]})


class _Topology(CommandEnv):
    def __init__(self, holders) -> None:
        super().__init__("http://127.0.0.1:1")
        self._holders = holders

    def servers(self):
        return self._holders


@pytest.mark.parametrize("exclude", [(), ("c:1",)], ids=["all", "one-dead"])
def test_pipelined_plan_gets_the_decoders_coefficients(exclude):
    """The chain is laid out from shard ids alone; rendered or applied, its
    hops carry exactly `decoder.repair_coefficients`' matrix."""
    from seaweedfs_tpu.shell import commands_ec
    from seaweedfs_tpu.storage.erasure_coding import decoder

    env = _Topology([_holder("a:1", [0, 1, 2, 4]), _holder("b:1", [5, 6, 7, 8]),
                     _holder("c:1", [9, 10]), _holder("d:1", [11, 12, 13])])
    plan = commands_ec.plan_rebuild_pipelined(env, 7, exclude=exclude)
    assert plan["missing"] == [3]
    assert all("coefs" not in hop for hop in plan["chain"])
    assert commands_ec.choose_rebuild_mode(plan)[0] == "pipelined"
    lines = commands_ec.describe_rebuild_pipelined(plan)
    assert lines and all("coefs" in hop for hop in plan["chain"])
    usable = sorted(s for sv in env.servers() if sv.id not in exclude
                    for s in sv.ec_shards[7])
    use, matrix = decoder.repair_coefficients(usable, plan["missing"])
    assert plan["use"] == use
    got = {int(s): c for hop in plan["chain"] for s, c in hop["coefs"].items()}
    assert got == {s: [int(matrix[0, i])] for i, s in enumerate(use)}
    # filled once: a second rendering leaves the hops as they are
    before = json.dumps(plan, sort_keys=True)
    commands_ec.fill_rebuild_coefficients(plan)
    assert json.dumps(plan, sort_keys=True) == before


def test_first_root_span_of_a_process_says_what_start_up_cost(
        cluster, monkeypatch):
    master, _ = cluster
    monkeypatch.setattr(shell_mod, "_startup_reported", False)
    rc, _ = run_here(master.url, "lock; unlock\ncluster.ps")
    assert rc == 0
    rc, _ = run_here(master.url, "lock; unlock")
    assert rc == 0
    roots = sorted(
        (s for t in trace.collector().traces(limit=10_000)
         for s in t["spans"]
         if s["role"] == "shell" and s["parent_id"] is None),
        key=lambda s: s["start"])[-5:]
    assert [s["name"] for s in roots] == [
        "shell lock", "shell unlock", "shell cluster.ps", "shell lock",
        "shell unlock"]
    first = roots[0]["attrs"]
    assert first["startup_s"] >= 0 and first["modules"] > 0
    for later in roots[1:]:
        assert "startup_s" not in later["attrs"]
        assert "modules" not in later["attrs"]


def test_the_cli_child_counts_start_up_from_its_own_start():
    """The CLI package stamps the clock as `python -m ...command.main`
    enters it, and the child's first verb reports the time since: no more
    than the child's whole life."""
    probe = (
        "from seaweedfs_tpu.command.main import main\n"
        "from seaweedfs_tpu.stats import trace\n"
        "main(['shell', '-master', '127.0.0.1:1', 'help'])\n"
        "root = trace.collector().traces(limit=1)[0]['spans'][0]\n"
        "print('ROOT', root['name'], root['attrs']['startup_s'],"
        " root['attrs']['modules'])\n")
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, "-c", probe], cwd=ROOT, text=True,
                          capture_output=True, timeout=120)
    lived = time.perf_counter() - t0
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("ROOT")]
    assert line, proc.stderr
    _, shell, verb, startup_s, modules = line[0].split()
    assert (shell, verb) == ("shell", "help")
    assert 0 < float(startup_s) < lived
    assert int(modules) > 50


SHELL_FILES = sorted(
    p.name for p in (ROOT / "seaweedfs_tpu/shell").glob("*.py"))


def _top_level_imports(tree: ast.Module) -> list[str]:
    """Modules imported when the file is, whatever `if` or `try` they sit
    in; not those inside a function."""
    names: list[str] = []

    def walk(nodes) -> None:
        for node in nodes:
            if isinstance(node, ast.Import):
                names.extend(a.name for a in node.names)
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names.append(node.module or "")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            else:
                for field in ("body", "orelse", "finalbody", "handlers"):
                    walk(getattr(node, field, []))

    walk(tree.body)
    return names


@pytest.mark.parametrize("name", SHELL_FILES)
def test_shell_modules_import_no_server_kernel_or_numpy_at_their_top(name):
    tree = ast.parse((ROOT / "seaweedfs_tpu/shell" / name).read_text())
    bad = [m for m in _top_level_imports(tree)
           if m.split(".")[0] in ("numpy", "jax")
           or m in ("seaweedfs_tpu.server", "seaweedfs_tpu.ops")
           or m.startswith(("seaweedfs_tpu.server.", "seaweedfs_tpu.ops."))]
    assert not bad, f"seaweedfs_tpu/shell/{name} imports {bad} at module level"


def test_the_import_lint_sees_what_it_should():
    tree = ast.parse(
        "import json\n"
        "try:\n    import numpy as np\nexcept ImportError:\n    np = None\n"
        "class K:\n    from seaweedfs_tpu.server import httpd\n"
        "def f():\n    import seaweedfs_tpu.ops.rs_kernel\n")
    assert _top_level_imports(tree) == [
        "json", "numpy", "seaweedfs_tpu.server"]
