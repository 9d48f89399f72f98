"""The system under test, driven the way its CLIs drive it:
builder main() -> saved folder -> ServiceContext.from_ini -> SearchServer on
a socket.  Copied from chip_smoke.py (PR 22: `write_bin`, `build_index`,
`ServerThread`, `served`, `query_text`, `no_serve_errors`, the device
check of `phase_device`); imports the program only inside functions, so
importing this module touches no backend.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


class HarnessError(RuntimeError):
    """The run cannot produce a result (no chip, a build that failed, a
    server that did not start): exit non-zero, print no result line."""


def require(cond, message: str) -> None:
    if not cond:
        raise HarnessError(message)


def peaks_for(kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    require(kind in table and kind != "source",
            f"device kind {kind!r} is not in benchmark/harness/peaks.json")
    return table[kind]


def check_device(chips: int) -> dict:
    """A TPU of a kind the peaks table knows, and enough chips — or no
    run.  Never a fallback."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    require(device["platform"] == "tpu",
            f"no TPU: jax.devices()[0].platform = {device['platform']!r}")
    peaks_for(device["kind"])
    require(len(devs) >= chips, f"{chips} chips asked, {len(devs)} found")
    return device


def write_bin(path: str, array: np.ndarray) -> None:
    """The reference's vectors.bin layout: int32 rows, int32 cols, rows."""
    with open(path, "wb") as f:
        f.write(np.asarray(array.shape, "<i4").tobytes())
        array.tofile(f)


def build_index(workdir: str, folder: str, data: np.ndarray,
                config: dict) -> float:
    """BIN file -> the builder CLI's main() -> saved `folder`.  Returns the
    seconds of index_builder.main."""
    from sptag_tpu.tools import index_builder

    bin_path = os.path.join(workdir, "vectors.bin")
    write_bin(bin_path, data)
    t0 = time.perf_counter()
    rc = index_builder.main(
        ["-d", str(data.shape[1]), "-v", config["value_type"], "-i",
         f"BIN:{bin_path}", "-o", folder, "-a", config["algo"],
         f"Index.DistCalcMethod={config['metric']}"]
        + [f"Index.{k}={v}" for k, v in config["index_params"].items()])
    seconds = time.perf_counter() - t0
    os.remove(bin_path)
    require(rc == 0, f"index_builder exited {rc}")
    return seconds


class ServerThread(threading.Thread):
    """An asyncio SearchServer on its own thread and loop (the boot-task
    reference is kept on purpose: a task nobody holds can be collected)."""

    def __init__(self, server):
        super().__init__(daemon=True, name="benchmark-server")
        self.server = server
        self.addr = None
        self.loop = None
        self._ready = threading.Event()

    def run(self):
        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)

        async def boot():
            self.addr = await self.server.start("127.0.0.1", 0)
            self._ready.set()

        self._boot_task = self.loop.create_task(boot())
        self.loop.run_forever()

    def wait_ready(self, timeout=60):
        require(self._ready.wait(timeout), "server did not start listening")
        return self.addr

    def stop(self):
        asyncio.run_coroutine_threadsafe(self.server.stop(),
                                         self.loop).result(timeout=60)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.join(timeout=60)


@contextlib.contextmanager
def served(workdir: str, name: str, folder: str, config: dict):
    """Serve one saved index the way `python -m sptag_tpu.serve.server`
    does, with the configuration's [Service] settings (none = the
    program's defaults: 2 ms window, max_batch 1024); yields
    (server, (host, port))."""
    from sptag_tpu.serve.server import SearchServer
    from sptag_tpu.serve.service import ServiceContext

    ini = os.path.join(workdir, f"{name}.ini")
    service = {"ListenAddr": "127.0.0.1", "ListenPort": "0",
               **config.get("service", {})}
    with open(ini, "w") as f:
        f.write("[Service]\n"
                + "".join(f"{k}={v}\n" for k, v in service.items())
                + f"[QueryConfig]\nDefaultMaxResultNumber={config['k']}\n"
                f"[Index]\nList={name}\n"
                f"[Index_{name}]\nIndexFolder={folder}\n")
    ctx = ServiceContext.from_ini(ini)
    # from_ini logs and skips an index that fails to load
    require(name in ctx.indexes, f"server did not load index {name!r}")
    server = SearchServer(ctx)
    thread = ServerThread(server)
    thread.start()
    try:
        yield server, thread.wait_ready()
    finally:
        thread.stop()


def query_text(name: str, k: int, vec: np.ndarray) -> str:
    body = "|".join(repr(float(x)) for x in vec)        # exact round trip
    return f"$resultnum:{k} $indexname:{name} {body}"


def serve_error_counts() -> dict:
    """The server answers a failed search with a status, and counts it."""
    from sptag_tpu.utils import metrics

    return {name: metrics.counter_value(name)
            for name in ("service.search_errors", "server.batch_failures",
                         "server.queue_full", "server.admission_sheds",
                         "server.deadline_drops",
                         "server.malformed_packets")}
