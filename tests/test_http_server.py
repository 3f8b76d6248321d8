"""The framework's own HTTP/1.1 server over real sockets: request
parsing, keep-alive, chunked bodies, protocol-error responses."""

import asyncio
import json

import httpx
import pytest

from mlapi_tpu.serving.asgi import App
from mlapi_tpu.serving.server import Server

pytestmark = pytest.mark.anyio


def make_app() -> App:
    app = App()

    @app.get("/ping")
    async def ping():
        return {"pong": True}

    @app.post("/echo")
    async def echo(request):
        return {"len": len(request.body), "body": request.body.decode("latin-1")}

    return app


@pytest.fixture()
async def server():
    srv = Server(make_app(), host="127.0.0.1", port=0)
    await srv.start()
    yield srv
    await srv.stop()


async def test_get_and_post_over_real_socket(server):
    async with httpx.AsyncClient(
        base_url=f"http://127.0.0.1:{server.port}"
    ) as client:
        r = await client.get("/ping")
        assert r.status_code == 200 and r.json() == {"pong": True}
        r = await client.post("/echo", content=b"hello")
        assert r.json() == {"len": 5, "body": "hello"}


async def test_keep_alive_reuses_connection(server):
    reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
    try:
        for i in range(3):
            writer.write(
                b"GET /ping HTTP/1.1\r\nhost: x\r\n\r\n"
            )
            await writer.drain()
            status = await reader.readline()
            assert b"200" in status
            headers = {}
            while True:
                line = await reader.readline()
                if line in (b"\r\n", b""):
                    break
                k, _, v = line.decode().partition(":")
                headers[k.strip().lower()] = v.strip()
            assert headers["connection"] == "keep-alive"
            body = await reader.readexactly(int(headers["content-length"]))
            assert json.loads(body) == {"pong": True}
    finally:
        writer.close()


async def test_chunked_request_body(server):
    reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
    try:
        writer.write(
            b"POST /echo HTTP/1.1\r\nhost: x\r\n"
            b"transfer-encoding: chunked\r\n\r\n"
            b"5\r\nhello\r\n6\r\n world\r\n0\r\n\r\n"
        )
        await writer.drain()
        raw = await reader.readuntil(b"\r\n\r\n")
        assert b"200" in raw.split(b"\r\n")[0]
        length = int(
            [l for l in raw.split(b"\r\n") if l.lower().startswith(b"content-length")][
                0
            ].split(b":")[1]
        )
        body = json.loads(await reader.readexactly(length))
        assert body == {"len": 11, "body": "hello world"}
    finally:
        writer.close()


async def test_malformed_request_line_400(server):
    reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
    try:
        writer.write(b"GARBAGE\r\n\r\n")
        await writer.drain()
        status = await reader.readline()
        assert b"400" in status
    finally:
        writer.close()


async def test_unsupported_protocol_501(server):
    reader, writer = await asyncio.open_connection("127.0.0.1", server.port)
    try:
        writer.write(b"GET /ping SPDY/3\r\n\r\n")
        await writer.drain()
        assert b"501" in await reader.readline()
    finally:
        writer.close()


async def test_connection_close_honored(server):
    async with httpx.AsyncClient(
        base_url=f"http://127.0.0.1:{server.port}"
    ) as client:
        r = await client.get("/ping", headers={"connection": "close"})
        assert r.status_code == 200
        assert r.headers["connection"] == "close"
