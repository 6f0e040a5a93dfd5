"""Record ``data/split_spans.xplane.pb``: a few steps of the
``mnist.train-split-queue`` cell's split training, at its shapes, under
the profiler, inside ``bench.window`` and ``bench.fit`` spans as the
benchmark's traced window has them.  Needs one TPU chip; from the
checkout's root:

    python3 bench/tests/record_split_spans.py [--out PATH] [--steps N]

The session is built, resolved and compiled by a first short ``fit``
before the profiler starts, so the trace holds the warm-up handshake
and the steps of one ``fit`` call, as the benchmark's window does.  The
profiler runs without its Python tracer (an event per Python call) and
with the host tracer at its first level (the spans, without most of the
runtime's own events), and the file leaves out the programs' HLO
(``DROPPED``): the readers need none of these, and they would make the
file several times larger.
"""
from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = "mnist.train-split-queue"
#: the plane of the programs' HLO, which the profiler writes even with
#: ``enable_hlo_proto`` off; no reader reads it
DROPPED = ("/host:metadata",)


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """``(field, start, end, body)`` of each top-level field of one
    serialized protobuf message; ``body`` only for length-delimited
    fields."""
    i = 0
    while i < len(buf):
        start = i
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        body = None
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 2:
            n, i = _varint(buf, i)
            body, i = buf[i:i + n], i + n
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {start}")
        yield field, start, i, body


def drop_planes(xspace: bytes, names=DROPPED) -> bytes:
    """A serialized ``XSpace`` without its planes (field 1) whose name
    (the plane's field 2) is in ``names``."""
    out = bytearray()
    for field, start, end, body in _fields(xspace):
        if field == 1 and any(f == 2 and b.decode() in names
                              for f, _, _, b in _fields(body)):
            continue
        out += xspace[start:end]
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="record_split_spans.py")
    ap.add_argument("--out", default=str(
        ROOT / "bench" / "tests" / "data" / "split_spans.xplane.pb"))
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import harness, trace
    from repro.federation import VerticalSession

    cell = harness.Cell(harness.load_benchmark(), CELL)
    harness.require_device("tpu", cell.chips)
    cfg, tr = cell.config, cell.traffic
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    data = cell.binding.Data(cfg, tr, args.seed, cell.reference)
    session = VerticalSession(data.scientist, data.owners, seed=args.seed)
    session.resolve(group=tr["resolve_group"])
    session.build(cell.binding.program_config(cfg), seed=0)
    fit_kw = dict(batch_size=tr["batch"], mode=tr["mode"],
                  backend=tr["backend"], compression=tr["compression"],
                  schedule=tr["schedule"], microbatches=tr["microbatches"],
                  verbose=False)
    session.fit(steps=tr["check_steps"], **fit_kw)
    out_dir = tempfile.mkdtemp(prefix="split-spans-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(out_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.fit"):
                session.fit(steps=args.steps, **fit_kw)
            jax.block_until_ready(session.params)
        jax.profiler.stop_trace()
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(trace.find_xplane(out_dir), "rb") as f:
            recorded = f.read()
        with open(args.out, "wb") as f:
            f.write(drop_planes(recorded))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes, "
          f"{args.steps} steps)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
