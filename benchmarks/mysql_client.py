"""A MySQL 4.1 text-protocol client on a raw socket: handshake and
COM_QUERY, nothing else. The benchmark's load generator; it imports
nothing of the program under test."""

from __future__ import annotations

import socket
import struct

_CLIENT_PROTOCOL_41 = 0x0200
_CLIENT_SECURE_CONNECTION = 0x8000


class ServerError(RuntimeError):
    """An ERR packet: the statement failed on the server."""

    def __init__(self, errno: int, message: str):
        super().__init__(f"server error {errno}: {message}")
        self.errno = errno


class MysqlClient:
    def __init__(self, port: int, host: str = "127.0.0.1", timeout_s: float = 1100.0):
        self.sock = socket.create_connection((host, port), timeout=timeout_s)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._rfile = self.sock.makefile("rb")
        self._seq = 0
        greeting = self._read_packet()
        if not greeting or greeting[0] != 0x0A:
            raise ConnectionError("expected handshake v10")
        caps = _CLIENT_PROTOCOL_41 | _CLIENT_SECURE_CONNECTION
        self._write_packet(
            struct.pack("<II", caps, 1 << 24) + bytes([0xFF]) + b"\x00" * 23
            + b"root\x00" + bytes([0])
        )
        ok = self._read_packet()
        if not ok or ok[0] != 0x00:
            raise ConnectionError(f"auth failed: {ok!r}")

    def _read_exact(self, n: int) -> bytes:
        data = self._rfile.read(n)
        if data is None or len(data) < n:
            raise ConnectionError("server closed the connection")
        return data

    def _read_packet(self) -> bytes:
        body = b""
        while True:
            head = self._read_exact(4)
            size = head[0] | head[1] << 8 | head[2] << 16
            self._seq = (head[3] + 1) & 0xFF
            body += self._read_exact(size)
            if size < 0xFFFFFF:
                return body

    def _write_packet(self, body: bytes) -> None:
        out = b""
        while True:
            part, body = body[:0xFFFFFF], body[0xFFFFFF:]
            out += struct.pack("<I", len(part))[:3] + bytes([self._seq]) + part
            self._seq = (self._seq + 1) & 0xFF
            if len(part) < 0xFFFFFF:
                break
        self.sock.sendall(out)

    @staticmethod
    def _lenenc(data: bytes, pos: int):
        v = data[pos]
        if v < 251:
            return v, pos + 1
        if v == 0xFC:
            return struct.unpack_from("<H", data, pos + 1)[0], pos + 3
        if v == 0xFD:
            return int.from_bytes(data[pos + 1:pos + 4], "little"), pos + 4
        return struct.unpack_from("<Q", data, pos + 1)[0], pos + 9

    def query(self, sql: str) -> list:
        """One statement; returns when the last row is fetched. Rows are
        tuples of text cells (None for NULL); a statement with no result
        set returns []."""
        self._seq = 0
        self._write_packet(b"\x03" + sql.encode())
        first = self._read_packet()
        if first[0] == 0xFF:
            errno = struct.unpack_from("<H", first, 1)[0]
            raise ServerError(errno, first[9:].decode(errors="replace"))
        if first[0] == 0x00:
            return []
        ncols, _ = self._lenenc(first, 0)
        for _ in range(ncols):
            self._read_packet()  # column definitions
        eof = self._read_packet()
        if eof[0] != 0xFE:
            raise ConnectionError("expected EOF after column definitions")
        rows = []
        while True:
            pkt = self._read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return rows
            if pkt[0] == 0xFF:
                errno = struct.unpack_from("<H", pkt, 1)[0]
                raise ServerError(errno, pkt[9:].decode(errors="replace"))
            row, pos = [], 0
            while pos < len(pkt):
                if pkt[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    ln, pos = self._lenenc(pkt, pos)
                    row.append(pkt[pos:pos + ln].decode())
                    pos += ln
            rows.append(tuple(row))

    def close(self) -> None:
        try:
            self._seq = 0
            self._write_packet(b"\x01")
        except OSError:
            pass
        for closer in (self._rfile.close, self.sock.close):
            try:
                closer()
            except OSError:
                pass
