"""``native_once.build_native_once`` (what this directory's conftest runs)
against the race it closes: processes that find no library compile it into
one temporary file and rename it into place. ``_compile`` is replaced by
one that does the same with its output but takes a second and needs no
g++, and the processes share one temporary build directory."""

import pathlib
import subprocess
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent.parent

CHILD = """
import os, pathlib, sys, time
sys.path[:0] = [{root!r}, {here!r}]
from cedar_tpu.native import build

build._BUILD_DIR = pathlib.Path({build_dir!r})


def slow_compile(out, glue_inc):
    tmp = out.with_suffix(".so.tmp")
    tmp.write_bytes(b"a library")
    with open(build._BUILD_DIR / "compiles", "a") as f:
        f.write(f"{{os.getpid()}}\\n")
    time.sleep(1.0)
    os.replace(tmp, out)


build._compile = slow_compile
from native_once import build_native_once

print(build_native_once(build._BUILD_DIR / ".build.lock"))
"""


@pytest.mark.parametrize("processes", [2, 6])
def test_processes_that_find_no_library_build_it_once_and_all_load_it(tmp_path, processes):
    build_dir = tmp_path / "_build"
    code = CHILD.format(root=str(ROOT), here=str(HERE), build_dir=str(build_dir))
    procs = [subprocess.Popen([sys.executable, "-c", code], stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True) for _ in range(processes)]
    outs = [p.communicate(timeout=120) for p in procs]
    assert [p.returncode for p in procs] == [0] * processes, [err[-800:] for _, err in outs]
    paths = {out.strip() for out, _ in outs}
    assert len(paths) == 1
    library = pathlib.Path(paths.pop())
    assert library.parent == build_dir and library.read_bytes() == b"a library"
    # one compile, and no temporary file left behind
    assert len((build_dir / "compiles").read_text().split()) == 1
    assert not list(build_dir.glob("*.tmp"))
