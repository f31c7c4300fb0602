"""A master in this process and a fleet member reduced to its control-plane
behaviour, over real localhost TCP: what the control-plane tests share."""

from __future__ import annotations

import asyncio
import time

from oobleck_tpu.config import OobleckArguments
from oobleck_tpu.elastic.master import OobleckMasterDaemon
from oobleck_tpu.elastic.message import (
    PROTOCOL_VERSION,
    RequestType,
    ResponseType,
    recv_msg,
    send_request,
)


async def start_master(port: int = 0):
    """(daemon, serve task) of a launcher-less master."""
    m = OobleckMasterDaemon(port=port, launcher=None)
    await m.start()
    return m, asyncio.create_task(m.serve_forever())


async def launch_job(port: int, node_ips) -> None:
    args = OobleckArguments()
    args.dist.node_ips = list(node_ips)
    r, w = await asyncio.open_connection("127.0.0.1", port)
    await send_request(w, RequestType.LAUNCH_JOB, {"args": args.to_dict()})
    assert (await recv_msg(r))["kind"] == ResponseType.SUCCESS.value
    w.close()


async def pool_rpc(port: int, payload: dict) -> dict:
    """One POOL_BORROW request (a borrow or a release) and its answer."""
    r, w = await asyncio.open_connection("127.0.0.1", port)
    await send_request(w, RequestType.POOL_BORROW, payload)
    msg = await recv_msg(r)
    w.close()
    return msg


class ScriptedAgent:
    """Registers and collects broadcasts."""

    def __init__(self, ip: str):
        self.ip = ip
        self.reader = None
        self.writer = None
        self.inbox: list[dict] = []
        self._drain: asyncio.Task | None = None

    async def register(self, port: int) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", port)
        await send_request(self.writer, RequestType.REGISTER_AGENT,
                           {"ip": self.ip, "protocol": PROTOCOL_VERSION,
                            "ping_interval": 10.0})
        msg = await recv_msg(self.reader)
        assert msg["kind"] == ResponseType.SUCCESS.value, msg
        self._start_drain()

    def _start_drain(self) -> None:
        async def _loop(reader):
            try:
                while True:
                    self.inbox.append(await recv_msg(reader, timeout=None))
            except (asyncio.IncompleteReadError, ConnectionError, OSError):
                pass

        self._drain = asyncio.ensure_future(_loop(self.reader))

    async def wait_verb(self, verbs: set[str], timeout: float) -> dict:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            for msg in self.inbox:
                if msg.get("kind") in verbs:
                    return msg
            await asyncio.sleep(0.01)
        raise TimeoutError(f"{self.ip}: no {verbs} broadcast in {timeout}s")

    def close(self) -> None:
        if self._drain is not None:
            self._drain.cancel()
        if self.writer is not None:
            self.writer.close()
