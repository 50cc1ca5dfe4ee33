"""Start ``python -m minio_tpu.server`` as the benchmark's child.

    python3 benchmark/launcher.py --cores 0-8 [--trace-dir D] [--break NAME] -- <server arguments>

The plain form pins the process to its cores and hands over to the program's
own entry point: nothing of the program is changed.  Two additions exist for
runs that are not timed:

``--trace-dir`` (the ``--trace 1`` run) wraps the calls into each layer with
``jax.profiler.TraceAnnotation`` from here - spans inside the program are a
later PR's - and takes a profiler trace while the file ``<D>/on`` exists.
Only this process holds the chip, so only it can trace.

``--break`` plants one fault under the served path, for the control and the
tests that have to see ``correct`` come out false.  The plain command of
``BENCHMARK.json`` cannot reach it.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (module, class or None, attribute, span name) - the calls into each layer
SPANS = [
    ("minio_tpu.utils.hashreader", "HashReader", "read", "hashreader_read"),
    ("minio_tpu.storage.xl", "XLStorage", "read_version", "xl_read_version"),
    ("minio_tpu.storage.xl", "XLStorage", "read_all", "xl_read_all"),
    ("minio_tpu.storage.xl", "XLStorage", "write_all", "xl_write_all"),
    ("minio_tpu.storage.xl", "XLStorage", "rename_data", "xl_rename_data"),
    ("minio_tpu.storage.xl", "XLStorage", "delete_version", "xl_delete_version"),
    ("minio_tpu.storage.xl", "XLStorage", "delete_file", "xl_delete_file"),
    ("minio_tpu.storage.xl", "_FileShardWriter", "write", "xl_shard_write"),
    ("minio_tpu.storage.xl", "_FileShardWriter", "close", "xl_shard_close_fsync"),
    ("minio_tpu.storage.xl", "_FileShardReader", "read_at", "xl_shard_read"),
    ("minio_tpu.codec.batcher", "BatchingBackend", "_run_group", "batch_run_group"),
    ("minio_tpu.codec.backend", "TpuBackend", "encode_digest_begin", "seam_encode_digest_begin"),
    ("minio_tpu.codec.backend", "TpuBackend", "encode_digest_end", "seam_encode_digest_end"),
    ("minio_tpu.codec.backend", "TpuBackend", "drain", "seam_drain"),
    ("minio_tpu.codec.backend", "TpuBackend", "digest", "seam_digest"),
    ("minio_tpu.codec.backend", "TpuBackend", "reconstruct", "seam_reconstruct"),
    ("minio_tpu.objectlayer.erasure_object", "ErasureObjects", "get_object", "ol_get_object"),
    ("minio_tpu.objectlayer.erasure_object", "ErasureObjects", "put_object", "ol_put_object"),
    ("minio_tpu.objectlayer.erasure_object", "ErasureObjects", "get_object_info", "ol_get_object_info"),
    ("minio_tpu.objectlayer.erasure_object", "ErasureObjects", "delete_object", "ol_delete_object"),
]
PREFIX = "bm/"  # every span of the benchmark's carries it, so the reduction finds them


def parse_cores(text: str) -> "set[int]":
    out: "set[int]" = set()
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.update(range(int(lo), int(hi or lo) + 1))
    return out


def _target(module: str, cls: "str | None"):
    import importlib

    mod = importlib.import_module(module)
    return getattr(mod, cls) if cls else mod


def install_spans() -> "list[str]":
    """Wrap each listed call in a TraceAnnotation; returns the names not found
    (a refactor of the program shows here, not as a silent gap)."""
    import jax

    missing = []
    for module, cls, attr, name in SPANS:
        try:
            owner = _target(module, cls)
            fn = getattr(owner, attr)
        except (ImportError, AttributeError):
            missing.append(name)
            continue

        def wrap(fn=fn, label=PREFIX + name):
            @functools.wraps(fn)
            def spanned(*a, **kw):
                with jax.profiler.TraceAnnotation(label):
                    return fn(*a, **kw)
            return spanned

        setattr(owner, attr, wrap())
    return missing


def trace_on_request(trace_dir: str) -> None:
    """Trace while ``<trace_dir>/on`` exists; ``<trace_dir>/done`` says the
    trace is written."""
    import jax

    flag = os.path.join(trace_dir, "on")

    def watch() -> None:
        while not os.path.exists(flag):
            time.sleep(0.02)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the interpreter's frames would swamp the host
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with open(os.path.join(trace_dir, "started"), "w") as f:
            f.write(repr(time.monotonic()))
        while os.path.exists(flag):
            time.sleep(0.02)
        t = time.monotonic()
        jax.profiler.stop_trace()
        with open(os.path.join(trace_dir, "done"), "w") as f:
            f.write(repr(t))

    threading.Thread(target=watch, daemon=True, name="bm-trace").start()


# ---------------------------------------------------------------------------
# faults for the control and the tests; never part of a timed run
# ---------------------------------------------------------------------------


def break_parity_zero() -> None:
    """The control of the cells that write: parity is acknowledged but never
    computed (the drain returns zeros), so an object no longer reads back from
    any k of its n shards."""
    import numpy as np

    from minio_tpu.codec import backend

    for cls in (backend._DeviceParityRef, backend._EagerParityRef):
        real = cls.drain

        def drain(self, real=real):
            return np.zeros_like(real(self))

        cls.drain = drain


def break_no_reconstruct() -> None:
    """The control of the degraded cell: lost shards are not rebuilt, the
    reader gets the rows as they came off the drives."""
    import numpy as np

    from minio_tpu.codec import backend

    def reconstruct(self, shards, present, data_shards, parity_shards):
        return np.ascontiguousarray(np.asarray(shards)[:, :data_shards])

    backend.TpuBackend.reconstruct = reconstruct


def break_flip_get() -> None:
    """An answer altered where it is produced: one byte of every decoded
    block changes on its way to the socket."""
    from minio_tpu.codec import erasure

    real = erasure.Erasure._write_blocks

    class Flipping:
        def __init__(self, inner):
            self.inner = inner

        def write(self, data):
            b = bytearray(data)
            if b:
                b[len(b) // 2] ^= 0x01
            return self.inner.write(bytes(b))

        def __getattr__(self, name):
            return getattr(self.inner, name)

    def write_blocks(self, writer, *a, **kw):
        return real(self, Flipping(writer), *a, **kw)

    erasure.Erasure._write_blocks = write_blocks


BREAKS = {
    "parity_zero": break_parity_zero,
    "no_reconstruct": break_no_reconstruct,
    "flip_get": break_flip_get,
}


def main(argv: "list[str]") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cores", required=True)
    ap.add_argument("--trace-dir")
    ap.add_argument("--break", dest="fault", choices=sorted(BREAKS))
    ap.add_argument("server", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    server_argv = args.server[1:] if args.server[:1] == ["--"] else args.server
    allowed = os.sched_getaffinity(0)
    os.sched_setaffinity(0, (parse_cores(args.cores) & allowed) or allowed)
    sys.path.insert(0, REPO)
    from minio_tpu.server.__main__ import main as server_main
    from minio_tpu.utils import jaxenv

    jaxenv.setup_compile_cache()  # before the first JAX use, as the program does
    if args.fault:
        BREAKS[args.fault]()
        print(f"launcher: fault planted: {args.fault}", flush=True)
    if args.trace_dir:
        missing = install_spans()
        print(f"launcher: spans installed, not found: {missing}", flush=True)
        trace_on_request(args.trace_dir)
    return server_main(server_argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
