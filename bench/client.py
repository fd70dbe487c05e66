"""The benchmark's in-process HTTP client for the node's ASGI app.

A request runs the app coroutine in the caller's own task, and a
streamed response is timestamped inside the app's ``send`` call, which is
the moment a socket server would write the frame to the wire.  So the
times the benchmark records are the server's, with no extra turns of the
event loop on the client's side.
"""
from __future__ import annotations

import asyncio
import json
from typing import Callable, Optional, Tuple


def _scope(method: str, path: str, body: bytes) -> dict:
    return {'type': 'http', 'asgi': {'version': '3.0'},
            'http_version': '1.1', 'method': method, 'scheme': 'http',
            'path': path, 'raw_path': path.encode(), 'query_string': b'',
            'headers': [(b'host', b'bench'),
                        (b'content-type', b'application/json'),
                        (b'content-length', str(len(body)).encode())],
            'client': ('bench', 0), 'server': ('bench', 80)}


async def request(app, method: str, path: str, obj=None, *,
                  on_frame: Optional[Callable[[dict], None]] = None
                  ) -> Tuple[int, object]:
    """One request; returns (status, parsed JSON body).  With
    ``on_frame``, each SSE ``data:`` frame of a streamed response is
    parsed and handed to it as the app sends it, and the body returned is
    None."""
    body = json.dumps(obj).encode() if obj is not None else b''
    sent = False
    done = asyncio.Event()
    status = [0]
    chunks = []
    buf = [b'']

    async def receive():
        nonlocal sent
        if not sent:
            sent = True
            return {'type': 'http.request', 'body': body,
                    'more_body': False}
        await done.wait()
        return {'type': 'http.disconnect'}

    async def send(msg):
        if msg['type'] == 'http.response.start':
            status[0] = msg['status']
            return
        data = msg.get('body', b'')
        if on_frame is None:
            chunks.append(data)
        elif data:
            buf[0] += data
            while b'\n\n' in buf[0]:
                frame, buf[0] = buf[0].split(b'\n\n', 1)
                for line in frame.split(b'\n'):
                    if line.startswith(b'data:'):
                        payload = line[5:].strip()
                        if payload != b'[DONE]':
                            on_frame(json.loads(payload))
        if not msg.get('more_body', False):
            done.set()

    try:
        await app(_scope(method, path, body), receive, send)
    finally:
        done.set()
    if on_frame is not None:
        return status[0], None
    raw = b''.join(chunks)
    return status[0], (json.loads(raw) if raw else None)
