"""Record ``data/v5e_scopes_tiny.xplane.pb``: the program's scopes and
spans on one chip, at tiny sizes.

Inside one ``bench.window`` it runs one episode of the tiny paper cell
(n=12, d=4096, 3 rounds of ``core.protocol.run_training``) and two tiny
Trainer rounds (``Trainer.run_round``, 2 layers, batches made before the
window), each under ``bench.round``; everything is compiled and warmed
up before the trace starts. The ``/host:metadata`` plane, which holds
the HLO protos of the programs and nothing that ``bench/spans.py`` or
``bench/trace.py`` reads, is left out of the file to keep it small, and
the checkout's path in the ops' source stats is blanked to a run of
``_`` of the same length, so that no length in the message changes. Run
on a machine with one TPU:

    python3 tests/bench/record_scopes_trace.py [out.xplane.pb]
"""
from __future__ import annotations

import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import bench_tiny  # noqa: E402  (puts the repository on the path)

OUT = HERE / "data" / "v5e_scopes_tiny.xplane.pb"
SEED = 3000000001
DROPPED_PLANE = "/host:metadata"


def without_plane(space: bytes, name: str) -> bytes:
    """The serialized XSpace ``space`` without its planes named ``name``
    (field 1 of XSpace, each plane's name its field 2); every other
    top-level field is copied byte for byte."""
    from bench.spans import _varint, fields

    buf, out, i = memoryview(space), bytearray(), 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        if key & 7 != 2:
            raise ValueError(f"XSpace field {key >> 3} is not "
                             f"length-delimited")
        size, i = _varint(buf, i)
        value, i = buf[i:i + size], i + size
        if key >> 3 == 1 and any(num == 2 and bytes(v).decode() == name
                                 for num, v in fields(value)):
            continue
        out += buf[start:i]
    return bytes(out)


def blanked(space: bytes, path: Path) -> bytes:
    """``space`` with every ``<path>/`` replaced by ``/___.../`` of the
    same length."""
    prefix = str(path).encode() + b"/"
    return space.replace(prefix, b"/" + b"_" * (len(prefix) - 2) + b"/")


def main(out: Path = OUT) -> None:
    import jax

    from bench import harness
    from bench.trace import find

    with tempfile.TemporaryDirectory() as tmp:
        root = bench_tiny.tiny_root(Path(tmp))
        bench = root / "bench"
        devices = harness.tpu_devices(1)
        cells = {}
        for name in ("protocol-tiny", "train-tiny"):
            wl = harness.workload(name, bench)
            cfg = harness.config(wl["config"], bench)
            cell = harness.driver(wl["driver"], bench).Cell(wl, cfg, SEED,
                                                            devices)
            cell.setup()
            cells[name] = cell
        proto, lm = cells["protocol-tiny"], cells["train-tiny"]
        batches = [lm._feed(lm.fed + i) for i in range(2)]
        jax.block_until_ready(batches)
        with harness.profiler_window(True, root / "trace") as trace_dir:
            with harness.annotate("bench.window"):
                proto.step()
                for batch in batches:
                    with harness.annotate("bench.round"):
                        lm.state, rec = lm.trainer.run_round(lm.state,
                                                             batch)
        out.parent.mkdir(parents=True, exist_ok=True)
        space = Path(find(str(trace_dir))).read_bytes()
        out.write_bytes(blanked(without_plane(space, DROPPED_PLANE),
                                bench_tiny.REPO))
    print(f"wrote {out} ({out.stat().st_size} bytes), last loss "
          f"{rec['loss']:.4f}")


if __name__ == "__main__":
    main(Path(sys.argv[1]) if len(sys.argv) > 1 else OUT)
