"""Native op build cache (ops/native/builder.py): the cached library is
keyed on a hash of its sources, flags and the host CPU, never on mtimes —
a library copied in from another machine (built ``-march=native`` there)
is rebuilt, not loaded."""

import os

import pytest

from deepspeed_tpu.ops.native import builder


@pytest.fixture
def op(tmp_path, monkeypatch):
    monkeypatch.setattr(builder, "BUILD_DIR", str(tmp_path))
    b = builder.CPUAdamBuilder()
    if not b.is_compatible():
        pytest.skip("no C++ compiler")
    return b


def test_library_without_key_is_rebuilt(op):
    os.makedirs(builder.BUILD_DIR, exist_ok=True)
    with open(op.so_path(), "wb") as f:
        f.write(b"not a library: stale copy from another machine")
    assert op.needs_build()          # newer mtime than the sources, no key
    op.build()
    assert not op.needs_build()
    with open(op.key_path()) as f:
        assert f.read() == op.build_key()
    assert os.path.getsize(op.so_path()) > 1000
    assert sorted(os.listdir(builder.BUILD_DIR)) == [
        "libcpu_adam.so", "libcpu_adam.so.key"]      # no temp files left


def test_key_covers_sources_flags_and_cpu(op, monkeypatch):
    key = op.build_key()
    with monkeypatch.context() as m:
        m.setattr(builder, "_host_cpu_flags", lambda: "another cpu")
        assert op.build_key() != key
    op.extra_flags.append("-DX=1")
    assert op.build_key() != key
    op.extra_flags.pop()
    assert op.build_key() == key
