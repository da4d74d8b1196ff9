"""The native library is built where it runs: the built file is named by a
digest of the sources and of the machine, so one built from other sources or
on another CPU is rebuilt, never loaded."""

from __future__ import annotations

import os
import shutil

from seaweedfs_tpu import native


def test_build_key_names_the_sources_content(tmp_path, monkeypatch):
    src = tmp_path / "src"
    src.mkdir()
    shutil.copy(os.path.join(native._SRC, "crc32c.cpp"), src / "crc32c.cpp")
    monkeypatch.setattr(native, "_SRC", str(src))
    key = native._build_key()
    assert key == native._build_key() and len(key) == 16
    with open(src / "crc32c.cpp", "a") as f:
        f.write("\n// one more line\n")
    assert native._build_key() != key


def test_missing_or_stale_library_is_rebuilt_before_loading(tmp_path, monkeypatch):
    here = tmp_path / "native"
    here.mkdir()
    # what a copy of another machine's tree would bring: a file under the
    # old fixed name and one under another key, neither loadable
    for stale in ("_seaweed_native.so", "_seaweed_native.0123456789abcdef.so"):
        (here / stale).write_bytes(b"built somewhere else")
    monkeypatch.setattr(native, "_HERE", str(here))
    monkeypatch.setattr(
        native, "load_info", {"path": None, "built_here": False, "error": None})
    lib = native._load()
    assert lib is not None, native.load_info
    want = f"_seaweed_native.{native._build_key()}.so"
    assert native.load_info == {
        "path": str(here / want), "built_here": True, "error": None}
    assert sorted(os.listdir(here)) == [want]
    assert lib.crc32c_update(0, b"123456789") == 0xE3069283
    # the next load finds it and builds nothing
    native.load_info["built_here"] = False
    assert native._load() is not None and native.load_info["built_here"] is False
