"""A streaming HTTP client on one thread: non-blocking sockets under a
selector, every ndjson line stamped on ``clock`` as it arrives."""
from __future__ import annotations

import json
import selectors
import socket
import time


class StreamClient:
    def __init__(self, host: str, port: int, clock=time.perf_counter):
        self.addr, self.clock = (host, port), clock
        self.sel = selectors.DefaultSelector()
        self.open = 0

    def send(self, record: dict, prompt, max_new: int) -> None:
        """POST /generate for ``record`` and start reading its stream.
        ``record`` gains sent/stamps/tokens/done/status/final."""
        body = json.dumps({"prompt": prompt, "max_new_tokens": int(max_new),
                           "request_id": "b%d" % record["index"]}).encode()
        head = ("POST /generate HTTP/1.0\r\nContent-Type: application/json"
                "\r\nContent-Length: %d\r\n\r\n" % len(body)).encode()
        record.update(stamps=[], tokens=[], done=None, status="inflight",
                      final=None, sent=None)
        try:
            sock = socket.create_connection(self.addr, timeout=10)
            sock.sendall(head + body)
        except OSError as e:
            now = self.clock()
            record.update(sent=now, done=now, status="failed",
                          error=repr(e))
            return
        record["sent"] = self.clock()
        sock.setblocking(False)
        self.sel.register(sock, selectors.EVENT_READ,
                          {"rec": record, "buf": b"", "head": True})
        self.open += 1

    def poll(self, timeout: float) -> list:
        """Wait up to ``timeout`` s for data; returns records that ended."""
        ended = []
        for key, _ in self.sel.select(max(0.0, timeout)):
            st, sock = key.data, key.fileobj
            try:
                chunk = sock.recv(65536)
            except BlockingIOError:
                continue
            except OSError as e:
                chunk, st["rec"]["error"] = b"", repr(e)
            now = self.clock()
            if chunk:
                st["buf"] += chunk
                self._parse(st, now)
            if not chunk or st["rec"]["done"] is not None:
                rec = st["rec"]
                if rec["done"] is None:       # closed with no terminal line
                    rec.update(done=now, status="failed")
                self._close(sock)
                ended.append(rec)
        return ended

    def _parse(self, st: dict, now: float) -> None:
        rec = st["rec"]
        if st["head"]:
            end = st["buf"].find(b"\r\n\r\n")
            if end < 0:
                return
            status = st["buf"].split(b"\r\n", 1)[0].split()
            rec["http"] = int(status[1]) if len(status) > 1 else 0
            st["buf"], st["head"] = st["buf"][end + 4:], False
        while b"\n" in st["buf"]:
            line, st["buf"] = st["buf"].split(b"\n", 1)
            if not line.strip():
                continue
            msg = json.loads(line)
            if rec.get("http") != 200:
                rec.update(done=now, error=msg.get("error"),
                           status="refused" if rec.get("http") == 503
                           else "failed")
            elif msg.get("done"):
                ok = msg.get("state") == "DONE" \
                    and msg.get("tokens") == rec["tokens"]
                rec.update(done=now, final=msg,
                           status="ok" if ok else "failed")
            else:
                rec["stamps"].append(now)
                rec["tokens"].append(msg["token"])

    def _close(self, sock) -> None:
        self.sel.unregister(sock)
        sock.close()
        self.open -= 1

    def abandon(self) -> None:
        """Hang up on every open stream (the server cancels them)."""
        for key in list(self.sel.get_map().values()):
            self._close(key.fileobj)
        self.sel.close()
