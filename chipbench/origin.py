"""Origin child: a range-serving HTTP server on loopback that counts the
bytes it serves and states the facts the check compares against.

    python3 chipbench/origin.py <config file> <seed> <port file>

``/o/<index>`` is object ``index`` of the configuration's generator (GET,
with or without Range); ``/stats`` the bytes served per object;
``/facts/<index>`` the object's length, its sha256 and the (sum32, xor32)
of each piece, computed here with NumPy from the generator's own bytes.
This process never imports the program under test or jax.
"""

from __future__ import annotations

import asyncio
import bisect
import collections
import concurrent.futures
import hashlib
import importlib
import json
import os
import re
import sys

import numpy as np

LOOPBACK = "127.0.0.1"
KEEP = 16                     # distinct objects held, newest kept
AHEAD = 4                     # made before they are asked for


def load_objects(config: dict, seed: int):
    """The generator the configuration names by ``object.kind``."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    module = importlib.import_module("objects." + config["object"]["kind"])
    return module.Objects(config, seed)


def piece_checksums(words: np.ndarray) -> tuple[int, int]:
    """(sum32, xor32) over little-endian uint32 words: the plain form."""
    return (int(np.sum(words, dtype=np.uint64) & 0xFFFFFFFF),
            int(np.bitwise_xor.reduce(words)) if words.size else 0)


class Blob:
    """One object as consecutive arrays, read by byte range."""

    def __init__(self, segments):
        self.parts = [np.ascontiguousarray(s) for s in segments]
        self.starts = [0]
        for p in self.parts:
            self.starts.append(self.starts[-1] + p.size)
        self.length = self.starts[-1]

    def read(self, start: int, length: int):
        """Bytes of the range: a view where one array holds it all."""
        end = min(start + length, self.length)
        i = bisect.bisect_right(self.starts, start) - 1
        if end <= self.starts[i + 1]:
            base = self.starts[i]
            return memoryview(self.parts[i])[start - base:end - base]
        out = []
        while start < end:
            part, base = self.parts[i], self.starts[i]
            take = part[start - base:end - base]
            out.append(take)
            start += take.size
            i += 1
        return np.concatenate(out).tobytes()

    def facts(self, piece_bytes: int, digest: str) -> dict:
        def sha() -> str:
            if digest != "sha256":
                return ""
            h = hashlib.sha256()
            for p in self.parts:
                h.update(p)
            return "sha256:" + h.hexdigest()

        def one(at: int) -> tuple[int, int]:
            raw = bytes(self.read(at, piece_bytes))
            raw += b"\0" * (-len(raw) % 4)
            return piece_checksums(np.frombuffer(raw, "<u4"))

        # hashlib and NumPy release the GIL: the hash runs beside the sums.
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            hashed = pool.submit(sha)
            sums = list(pool.map(one, range(0, self.length, piece_bytes)))
            return {"length": self.length, "digest": hashed.result(),
                    "piece_bytes": piece_bytes, "checksums": sums}


def parse_range(header: str, total: int) -> tuple[int, int]:
    m = re.fullmatch(r"bytes=(\d*)-(\d*)", header.strip())
    if not m or (not m.group(1) and not m.group(2)):
        raise ValueError(header)
    if not m.group(1):
        n = min(int(m.group(2)), total)
        return total - n, n
    start = int(m.group(1))
    end = min(int(m.group(2)), total - 1) if m.group(2) else total - 1
    if start > end:
        raise ValueError(header)
    return start, end - start + 1


def main(argv: list[str]) -> int:
    from aiohttp import web

    config_file, seed, port_file = argv[0], int(argv[1]), argv[2]
    with open(config_file) as f:
        config = json.load(f)
    objects = load_objects(config, seed)
    blobs: "collections.OrderedDict[int, asyncio.Future]" = \
        collections.OrderedDict()
    facts_of: dict[int, dict] = {}
    stats: dict[str, dict] = {}

    async def blob_of(index: int) -> Blob:
        fut = blobs.get(index)
        if fut is None:
            fut = asyncio.ensure_future(asyncio.to_thread(
                lambda: Blob(objects.segments(index))))
            blobs[index] = fut
            while len(blobs) > KEEP:
                blobs.popitem(last=False)
        return await fut

    async def serve_object(request):
        index = int(request.match_info["index"])
        body = await blob_of(index)
        if objects.distinct:
            # Clients take indices in order: the next few are made now, in
            # threads, so that making an object is never part of a pull.
            for ahead in range(index + 1, index + 1 + AHEAD):
                if ahead not in blobs:
                    asyncio.ensure_future(blob_of(ahead))
        stat = stats.setdefault(str(index), {"bytes": 0, "requests": 0})
        stat["requests"] += 1
        hdr = request.headers.get("Range")
        if not hdr:
            stat["bytes"] += body.length
            return web.Response(body=body.read(0, body.length),
                                headers={"Accept-Ranges": "bytes"})
        try:
            start, length = parse_range(hdr, body.length)
        except ValueError:
            return web.Response(status=416)
        data = body.read(start, length)
        stat["bytes"] += len(data)
        return web.Response(status=206, body=data, headers={
            "Accept-Ranges": "bytes",
            "Content-Range":
                f"bytes {start}-{start + len(data) - 1}/{body.length}"})

    async def facts(index: int) -> dict:
        if index not in facts_of:
            body = await blob_of(index)
            facts_of[index] = await asyncio.to_thread(
                body.facts, int(config["object"]["piece_bytes"]),
                config["object"].get("digest", ""))
        return facts_of[index]

    async def serve_facts(request):
        return web.json_response(
            await facts(int(request.match_info["index"])))

    async def serve():
        app = web.Application()
        app.router.add_get("/o/{index}", serve_object)
        app.router.add_get("/facts/{index}", serve_facts)
        app.router.add_get("/stats", lambda _: web.json_response(stats))
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, LOOPBACK, 0)
        await site.start()
        port = site._server.sockets[0].getsockname()[1]
        with open(port_file + ".tmp", "w") as f:
            f.write(str(port))
        os.replace(port_file + ".tmp", port_file)
        if objects.distinct:
            for index in range(AHEAD):
                asyncio.ensure_future(blob_of(index))
        else:                         # one object: made before it is asked
            await facts(0)
        if "jax" in sys.modules or any(
                m.startswith("dragonfly2_tpu") for m in sys.modules):
            raise RuntimeError("the origin child imported jax or the program")
        await asyncio.Event().wait()

    asyncio.run(serve())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
