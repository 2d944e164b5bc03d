"""Frame-codec parity: the native codec (librt_codec.so) and the
pure-Python fallback must produce byte-identical streams and identical
frame boundaries on every input — split headers, coalesced bursts, empty
payloads, oversized-length rejection — and the whole runtime must work
with the fallback forced (``RAY_TPU_DISABLE_NATIVE_CODEC=1``)."""

import os
import pickle
import random
import socket
import struct
import subprocess
import sys

import pytest

from ray_tpu.core import protocol


def _py_codec():
    return protocol.PythonCodec()


def _codecs():
    """Both codecs when the native build is available, else just python."""
    codecs = [_py_codec()]
    if protocol.NATIVE_CODEC_ACTIVE:
        codecs.append(protocol._codec)
    return codecs


def _random_msgs(rng, n):
    out = []
    for i in range(n):
        kind = rng.randrange(4)
        if kind == 0:
            out.append({"t": "done", "task_id": rng.randbytes(16),
                        "ok": True, "inline": {"aa": rng.randbytes(
                            rng.randrange(0, 3000))}})
        elif kind == 1:
            out.append({"t": "request", "rid": i, "op": "get",
                        "ids": [rng.randbytes(20).hex()
                                for _ in range(rng.randrange(0, 5))]})
        elif kind == 2:
            out.append([])  # minimal payload
        else:
            out.append({"t": "blob", "data": rng.randbytes(
                rng.randrange(0, 1 << 16))})
    return out


def test_encode_parity_fuzz():
    rng = random.Random(1234)
    for trial in range(10):
        msgs = _random_msgs(rng, rng.randrange(1, 40))
        payloads = [pickle.dumps(m, protocol=5) for m in msgs]
        streams = [bytes(c.encode(payloads)) for c in _codecs()]
        assert all(s == streams[0] for s in streams)
        # stream structure is the documented wire format
        (first_len,) = struct.unpack_from("<Q", streams[0], 0)
        assert first_len == len(payloads[0])


def test_scan_parity_fuzz_random_splits():
    """Same frames found regardless of how the stream is chunked — split
    headers, split payloads, coalesced bursts."""
    rng = random.Random(99)
    for trial in range(10):
        msgs = _random_msgs(rng, rng.randrange(1, 30))
        payloads = [pickle.dumps(m, protocol=5) for m in msgs]
        stream = bytes(_py_codec().encode(payloads))
        for codec in _codecs():
            # whole-stream scan
            frames, consumed = codec.scan(bytearray(stream), len(stream))
            assert consumed == len(stream)
            assert [bytes(stream[o:o + l]) for o, l in frames] == payloads
            # incremental scan with random chunk sizes
            buf = bytearray()
            got = []
            pos = 0
            while pos < len(stream):
                step = rng.randrange(1, 4096)
                buf += stream[pos:pos + step]
                pos += step
                frames, consumed = codec.scan(buf, len(buf))
                got += [bytes(buf[o:o + l]) for o, l in frames]
                del buf[:consumed]
            assert got == payloads
            assert not buf


def test_scan_empty_payload_frames():
    # zero-length payloads are legal at the framing layer
    raw = struct.pack("<Q", 0) * 3 + struct.pack("<Q", 2) + b"hi"
    for codec in _codecs():
        frames, consumed = codec.scan(bytearray(raw), len(raw))
        assert [l for _, l in frames] == [0, 0, 0, 2]
        assert consumed == len(raw)


def test_scan_partial_header_and_payload():
    payload = pickle.dumps({"x": 1}, protocol=5)
    frame = struct.pack("<Q", len(payload)) + payload
    for codec in _codecs():
        for cut in (0, 1, 7, 8, 9, len(frame) - 1):
            frames, consumed = codec.scan(bytearray(frame[:cut]), cut)
            assert frames == [] and consumed == 0
        frames, consumed = codec.scan(bytearray(frame), len(frame))
        assert len(frames) == 1 and consumed == len(frame)


def test_oversized_length_rejected_by_both_codecs():
    bad = bytearray(struct.pack("<Q", protocol.MAX_FRAME_BYTES + 1) + b"xy")
    for codec in _codecs():
        with pytest.raises(protocol.ProtocolError):
            codec.scan(bad, len(bad))
    # drain_frames surfaces it too (connection teardown path)
    with pytest.raises(protocol.ProtocolError):
        protocol.drain_frames(bad, lambda m: None, lambda: True)


def test_drain_frames_compacts_once_and_stops_on_dead():
    msgs = [{"i": i} for i in range(20)]
    payloads = [pickle.dumps(m, protocol=5) for m in msgs]
    buf = bytearray(_py_codec().encode(payloads))
    seen = []

    def handle(m):
        seen.append(m["i"])

    # alive() goes false after 5 messages: the rest must stay buffered
    protocol.drain_frames(buf, handle, lambda: len(seen) < 5)
    assert seen == [0, 1, 2, 3, 4]
    protocol.drain_frames(buf, handle, lambda: True)
    assert seen == list(range(20))
    assert not buf


def test_frame_reader_over_socketpair():
    a, b = socket.socketpair()
    try:
        reader = protocol.FrameReader(b, chunk_size=4096)
        msgs = [{"i": i, "pad": bytes(i * 7)} for i in range(64)]
        protocol.send_msgs(a, msgs)
        got = [reader.recv_msg() for _ in range(64)]
        assert [g["i"] for g in got] == list(range(64))
        # byte-dribbled frame (split header) reassembles
        payload = pickle.dumps({"t": "split"}, protocol=5)
        frame = struct.pack("<Q", len(payload)) + payload
        for i in range(len(frame)):
            a.sendall(frame[i:i + 1])
        assert reader.recv_msg() == {"t": "split"}
        a.close()
        assert reader.recv_msg() is None
    finally:
        b.close()


def test_recv_exact_recv_into_path():
    a, b = socket.socketpair()
    try:
        a.sendall(b"abcdef")
        assert bytes(protocol.recv_exact(b, 6)) == b"abcdef"
        a.close()
        assert protocol.recv_exact(b, 1) is None
    finally:
        b.close()


def test_native_build_graceful_fallback(monkeypatch, capsys):
    from ray_tpu.native import build

    with pytest.raises(build.NativeBuildError):
        build.lib_path("no_such_lib")
    # unknown name via the graceful path warns (once) and returns None
    build._warned.discard("no_such_lib")
    assert build.try_lib_path("no_such_lib") is None
    assert "pure-Python fallback" in capsys.readouterr().err
    # a missing compiler degrades the same way rather than crashing
    monkeypatch.setattr(build, "_LIBS",
                        {"codec": ("frame_codec.cc", "librt_x.so")})
    monkeypatch.setattr(build.subprocess, "run",
                        lambda *a, **k: (_ for _ in ()).throw(
                            FileNotFoundError("g++ not found")))
    build._warned.discard("codec")
    assert build.try_lib_path("codec") is None


def test_native_build_is_stale_by_content_not_mtime(tmp_path, monkeypatch):
    """A binary is rebuilt when the source it was built from differs from
    the source now, whatever the mtimes say: a copy of the tree resets
    them, and the copy must not run whatever binary was lying there."""
    from ray_tpu.native import build

    src = tmp_path / "src" / "one.cc"
    src.parent.mkdir()
    src.write_text('extern "C" int answer() { return 1; }\n')
    monkeypatch.setattr(build, "_DIR", str(tmp_path))
    monkeypatch.setattr(build, "_LIBS", {"one": ("one.cc", "libone.so")})

    lib = build.lib_path("one")
    built_from = build._built_from(lib)
    assert built_from == build._source_hash(str(src))
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, (0, 0))  # the source now looks OLDER than the binary
    assert build.lib_path("one") == lib
    assert build._built_from(lib) == build._source_hash(str(src))
    assert build._built_from(lib) != built_from
    import ctypes

    assert ctypes.CDLL(lib).answer() == 2


def test_fallback_runtime_end_to_end():
    """Dedicated fallback-viability run: a representative workload (tasks,
    actor calls, store round trip, error propagation) in a subprocess with
    the native codec disabled — every process in the tree (driver, raylet,
    workers) must select the pure-Python codec."""
    script = r"""
import os
assert os.environ["RAY_TPU_DISABLE_NATIVE_CODEC"] == "1"
from ray_tpu.core import protocol
assert not protocol.NATIVE_CODEC_ACTIVE
import numpy as np
import ray_tpu
ray_tpu.init(num_cpus=2)

@ray_tpu.remote
def sq(x):
    from ray_tpu.core import protocol as p
    assert not p.NATIVE_CODEC_ACTIVE  # worker subprocess fell back too
    return x * x

@ray_tpu.remote
class Counter:
    def __init__(self):
        self.n = 0
    def inc(self):
        self.n += 1
        return self.n

assert ray_tpu.get([sq.remote(i) for i in range(64)]) == \
    [i * i for i in range(64)]
c = Counter.remote()
assert ray_tpu.get([c.inc.remote() for _ in range(32)]) == \
    list(range(1, 33))
big = ray_tpu.put(np.arange(1 << 17))  # 1MB -> shm store
assert int(ray_tpu.get(big)[12345]) == 12345

@ray_tpu.remote
def boom():
    raise ValueError("expected")
try:
    ray_tpu.get(boom.remote())
    raise SystemExit("error did not propagate")
except Exception:
    pass
ray_tpu.shutdown()
print("FALLBACK_E2E_OK")
"""
    env = dict(os.environ)
    env["RAY_TPU_DISABLE_NATIVE_CODEC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "FALLBACK_E2E_OK" in proc.stdout
