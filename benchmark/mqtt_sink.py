"""Minimal MQTT 3.1.1 sink: the broker end of the server's QoS-0 publisher.

One selector thread accepts the server's connections (one per pipeline
instance), answers CONNECT with CONNACK and PINGREQ with PINGRESP, and for
every PUBLISH stores ``(arrival time, topic, payload bytes)``. Nothing is
parsed on arrival: the payloads are decoded after the measured window, so
the sink costs the machine one recv and one append per message.

The arrival time is ``time.time()``, the clock of the status payload's
``start_time`` (same machine), so a latency needs no clock conversion.
"""

from __future__ import annotations

import selectors
import socket
import threading
import time


class _Conn:
    __slots__ = ("sock", "buf")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.buf = bytearray()


def parse_packets(buf: bytearray) -> list[tuple[int, bytes]]:
    """Split complete MQTT packets off the front of ``buf`` (consumed in
    place). Returns ``(first byte, body)`` pairs."""
    out = []
    pos = 0
    n = len(buf)
    while pos + 2 <= n:
        length = 0
        shift = 0
        i = pos + 1
        done = False
        while i < n and i < pos + 5:
            b = buf[i]
            length |= (b & 0x7F) << shift
            shift += 7
            i += 1
            if not b & 0x80:
                done = True
                break
        if not done or i + length > n:
            break
        out.append((buf[pos], bytes(buf[i:i + length])))
        pos = i + length
    if pos:
        del buf[:pos]
    return out


class MqttSink:
    """Listens on 127.0.0.1 at a free port; ``messages`` grows by one
    ``(t_arrival, topic, payload)`` per PUBLISH."""

    def __init__(self) -> None:
        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(128)
        self._srv.setblocking(False)
        self.port = self._srv.getsockname()[1]
        self.messages: list[tuple[float, str, bytes]] = []
        #: topic -> arrival time of its first message
        self.first_seen: dict[str, float] = {}
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._srv, selectors.EVENT_READ, None)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._loop, name="mqtt-sink", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            for key, _ in self._sel.select(timeout=0.2):
                if key.data is None:
                    try:
                        sock, _ = self._srv.accept()
                    except OSError:
                        continue
                    sock.setblocking(False)
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                    self._sel.register(sock, selectors.EVENT_READ, _Conn(sock))
                    continue
                conn: _Conn = key.data
                try:
                    chunk = conn.sock.recv(262144)
                except BlockingIOError:
                    continue
                except OSError:
                    chunk = b""
                now = time.time()
                if not chunk:
                    self._sel.unregister(conn.sock)
                    conn.sock.close()
                    continue
                conn.buf += chunk
                for head, body in parse_packets(conn.buf):
                    kind = head >> 4
                    if kind == 3:  # PUBLISH, QoS 0: topic then payload
                        tlen = (body[0] << 8) | body[1]
                        topic = body[2:2 + tlen].decode("utf-8", "replace")
                        self.messages.append((now, topic, body[2 + tlen:]))
                        if topic not in self.first_seen:
                            self.first_seen[topic] = now
                    elif kind == 1:  # CONNECT -> CONNACK accepted
                        self._send(conn, b"\x20\x02\x00\x00")
                    elif kind == 12:  # PINGREQ -> PINGRESP
                        self._send(conn, b"\xd0\x00")
                    # DISCONNECT and anything else: nothing to answer

    @staticmethod
    def _send(conn: _Conn, data: bytes) -> None:
        try:
            conn.sock.sendall(data)
        except OSError:
            pass

    def close(self) -> None:
        self._stop.set()
        self._thread.join(5.0)
        for key in list(self._sel.get_map().values()):
            try:
                key.fileobj.close()
            except OSError:
                pass
        self._sel.close()
