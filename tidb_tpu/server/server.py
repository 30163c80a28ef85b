"""Threaded MySQL-protocol server over the embedded engine.

Reference: pkg/server/server.go:429 (Server.Run accept loop) +
conn.go:1009 (clientConn.Run read-dispatch loop), one goroutine per
connection; here one thread per connection, all sharing the catalog (the
device engine serializes on the single jit dispatch path: one chip per
process, or with `mesh_devices=N` the N chips of one host as one mesh).
"""

from __future__ import annotations

import socket
import socketserver
import threading
from typing import Optional

from tidb_tpu.obs.flight import FLIGHT
from tidb_tpu.server import protocol as P
from tidb_tpu.session import Result, Session
from tidb_tpu.storage import Catalog
from tidb_tpu.utils import racecheck

COM_QUIT = 0x01
COM_INIT_DB = 0x02
COM_QUERY = 0x03
COM_FIELD_LIST = 0x04
COM_PING = 0x0E
COM_STMT_PREPARE = 0x16
COM_STMT_EXECUTE = 0x17
COM_STMT_SEND_LONG_DATA = 0x18
COM_STMT_CLOSE = 0x19
COM_STMT_RESET = 0x1A
COM_STMT_FETCH = 0x1C


class Server:
    def __init__(
        self,
        catalog: Optional[Catalog] = None,
        host: str = "127.0.0.1",
        port: int = 4000,
        status_port: Optional[int] = None,
        dcn_scheduler=None,
        mesh_devices: Optional[int] = None,
    ):
        self.catalog = catalog or Catalog()
        # MPP mode (tidb_server --mesh-devices N): every connection's
        # session runs its statements as one SPMD program over the
        # process's ONE mesh of N devices and its one set of resident
        # shards. A width JAX cannot give raises here, before anything
        # is served; None is the one-device server
        self.mesh_devices = mesh_devices or None
        if self.mesh_devices:
            from tidb_tpu.parallel.mesh import shared_mesh

            shared_mesh(self.mesh_devices)
        self.host = host
        self.port = port
        # serving tier (PR 8): with a DCNFragmentScheduler attached,
        # every connection's session routes fragmentable/shuffleable
        # SELECTs across the worker fleet, gated by the scheduler's
        # admission controller — the MySQL front end becomes a
        # multi-tenant entry point to the fleet instead of a funnel
        # into one local engine
        self.dcn_scheduler = dcn_scheduler
        self._next_conn_id = [0]
        self._active_conns = 0
        self._lock = racecheck.make_lock("server.conns")
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self) -> None:
                outer._handle_conn(self.request)

        class TCP(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._tcp = TCP((host, port), Handler)
        self.port = self._tcp.server_address[1]
        # background stats owner (reference: domain's stats handle loop)
        from tidb_tpu.stats.handle import StatsHandle
        from tidb_tpu.utils.ttl import TTLWorker

        self.stats_handle = StatsHandle(self.catalog, interval_s=30.0)
        self.ttl_worker = TTLWorker(self.catalog, interval_s=60.0)
        # side HTTP port: /status /metrics /schema /settings (reference
        # pkg/server/http_status.go); None disables
        self.status_server = None
        if status_port is not None:
            from tidb_tpu.server.http_status import StatusServer

            self.status_server = StatusServer(
                self.catalog, host=host, port=status_port,
                connections=lambda: self.connections,
            )

    def serve_forever(self) -> None:
        self.stats_handle.start()
        self.ttl_worker.start()
        if self.status_server is not None:
            self.status_server.start_background()
        self._tcp.serve_forever()

    def start_background(self) -> threading.Thread:
        th = threading.Thread(
            target=self.serve_forever, daemon=True,
            name=f"mysql-serve-{self.port}",
        )
        th.start()
        return th

    def shutdown(self) -> None:
        if self.status_server is not None:
            self.status_server.shutdown()
        self.ttl_worker.stop()
        self.stats_handle.stop()
        self._tcp.shutdown()
        self._tcp.server_close()

    @property
    def connections(self) -> int:
        """Live client connection count (reference: Server.
        ConnectionCount feeding the /status handler)."""
        with self._lock:
            return self._active_conns

    # ------------------------------------------------------------------
    def _handle_conn(self, sock: socket.socket) -> None:
        with self._lock:
            self._active_conns += 1
        try:
            self._handle_conn_inner(sock)
        finally:
            with self._lock:
                self._active_conns -= 1

    def _handle_conn_inner(self, sock: socket.socket) -> None:
        io = P.PacketIO(sock)
        with self._lock:
            self._next_conn_id[0] += 1
            conn_id = self._next_conn_id[0]
        sess = Session(self.catalog, mesh_devices=self.mesh_devices)
        version = str(sess.vars.get("version"))
        scramble = P.new_scramble()
        io.write_packet(P.handshake_v10(conn_id, version, scramble))
        body = io.read_packet()
        if body is None:
            return
        try:
            user, db, auth = P.parse_handshake_response(body)
        except Exception:
            io.write_packet(P.err_packet(1045, "malformed handshake"))
            return
        # real authentication (reference: pkg/privilege auth at
        # clientConn.openSessionAndDoAuth) — mysql_native_password
        # against the catalog's user store
        if not self.catalog.users.authenticate(user, scramble, auth):
            io.write_packet(
                P.err_packet(
                    1045, f"Access denied for user '{user}'@'%'", "28000"
                )
            )
            return
        sess.user = user.lower()
        if db:
            sess.db = db.lower()
        if self.dcn_scheduler is not None:
            sess.attach_dcn_scheduler(self.dcn_scheduler)
        io.write_packet(P.ok_packet())

        # prepared statements: per-connection registry (reference:
        # conn_stmt.go handleStmtPrepare/Execute at conn.go:1999)
        stmts = {}
        next_stmt_id = [0]

        while True:
            io.reset_seq()
            body = io.read_packet()
            if body is None or not body:
                return
            cmd, payload = body[0], body[1:]
            try:
                if cmd == COM_QUIT:
                    return
                if cmd == COM_PING:
                    io.write_packet(P.ok_packet())
                elif cmd == COM_INIT_DB:
                    sess.execute(f"use `{payload.decode()}`")
                    io.write_packet(P.ok_packet())
                elif cmd == COM_QUERY:
                    from tidb_tpu.utils.failpoint import inject

                    # the served statement's root span: the command
                    # packet is read, until the answer's last packet
                    # is written (obs/flight.py SPANS)
                    with FLIGHT.span("stmt"):
                        inject("server/dispatch-query")
                        sql = payload.decode("utf-8", "replace")
                        self._run_query(io, sess, sql)
                elif cmd == COM_FIELD_LIST:
                    io.write_packet(P.eof_packet())
                elif cmd == COM_STMT_PREPARE:
                    sql = payload.decode("utf-8", "replace")
                    nparams = P.count_placeholders(sql)
                    next_stmt_id[0] += 1
                    sid = next_stmt_id[0]
                    # session-level parameterized plan (plan_cache.go
                    # analog): EXECUTE binds values as runtime inputs of
                    # the cached compiled plan instead of re-planning
                    # re-rendered SQL text
                    sess.prepare(f"__c{sid}", sql)
                    stmts[sid] = [sql, nparams, None]  # [sql, n, param types]
                    io.write_packet(P.stmt_prepare_ok(sid, 0, nparams))
                    if nparams:
                        for _ in range(nparams):
                            io.write_packet(P.column_def("?", None))
                        io.write_packet(P.eof_packet())
                elif cmd == COM_STMT_EXECUTE:
                    import struct as _st

                    # a root span like COM_QUERY's: the spans below it
                    # (session, or the prepared fast path's launch) hang
                    # from one statement's tree
                    with FLIGHT.span("stmt"):
                        sid = _st.unpack_from("<I", payload, 0)[0]
                        if sid not in stmts:
                            io.write_packet(
                                P.err_packet(1243, "unknown stmt")
                            )
                            continue
                        sql, nparams, ptypes = stmts[sid][:3]
                        _sid, params, ptypes = P.parse_stmt_execute(
                            payload, nparams, ptypes
                        )
                        stmts[sid][2] = ptypes
                        r = sess.execute_prepared(f"__c{sid}", params)
                        # CURSOR_TYPE_READ_ONLY: buffer the resultset and
                        # answer column defs only; rows stream through
                        # COM_STMT_FETCH (reference conn_stmt.go:153
                        # useCursor — JDBC setFetchSize & BI tools)
                        flags = payload[4] if len(payload) > 4 else 0
                        if (flags & P.CURSOR_TYPE_READ_ONLY) and r.columns:
                            types = (
                                getattr(r, "types", None)
                                or [None] * len(r.columns)
                            )
                            while len(stmts[sid]) < 4:
                                stmts[sid].append(None)
                            stmts[sid][3] = [list(r.rows), types, 0]
                            io.write_packet(P.lenenc_int(len(r.columns)))
                            for name, t in zip(r.columns, types):
                                io.write_packet(P.column_def(name, t))
                            io.write_packet(
                                P.eof_packet(P.SERVER_STATUS_CURSOR_EXISTS)
                            )
                        else:
                            self._write_result(io, r, binary=True, sess=sess)
                elif cmd == COM_STMT_FETCH:
                    import struct as _st

                    fsid = _st.unpack_from("<I", payload, 0)[0]
                    nfetch = _st.unpack_from("<I", payload, 4)[0]
                    ent = stmts.get(fsid)
                    cur = ent[3] if ent is not None and len(ent) > 3 else None
                    if cur is None:
                        io.write_packet(
                            P.err_packet(1243, "no open cursor for stmt")
                        )
                        continue
                    rows, types, pos = cur
                    chunk = rows[pos : pos + max(nfetch, 1)]
                    for row in chunk:
                        io.write_packet(P.binary_row(row, types))
                    cur[2] = pos + len(chunk)
                    if cur[2] >= len(rows):
                        ent[3] = None  # drained: close the cursor
                        io.write_packet(
                            P.eof_packet(P.SERVER_STATUS_LAST_ROW_SENT)
                        )
                    else:
                        io.write_packet(
                            P.eof_packet(P.SERVER_STATUS_CURSOR_EXISTS)
                        )
                elif cmd == COM_STMT_CLOSE:
                    import struct as _st

                    csid = _st.unpack_from("<I", payload, 0)[0]
                    if stmts.pop(csid, None) is not None:
                        try:
                            sess.deallocate(f"__c{csid}")
                        except ValueError:
                            pass
                    # no response by protocol
                elif cmd == COM_STMT_RESET:
                    import struct as _st

                    rsid = _st.unpack_from("<I", payload, 0)[0]
                    ent = stmts.get(rsid)
                    if ent is not None and len(ent) > 3:
                        ent[3] = None  # drop any open cursor
                    io.write_packet(P.ok_packet())
                else:
                    io.write_packet(
                        P.err_packet(1047, f"unsupported command {cmd:#x}")
                    )
            except Exception as e:  # error -> ERR packet, connection lives
                try:
                    # serving-tier admission verdicts (and anything
                    # else that declares one) carry their own MySQL
                    # error number — a rejected statement must read as
                    # a deliberate server verdict, not a generic 1105
                    errno = int(getattr(e, "mysql_errno", 0) or 1105)
                    io.write_packet(P.err_packet(errno, str(e)))
                except OSError:
                    return

    def _run_query(
        self, io: P.PacketIO, sess: Session, sql: str, binary: bool = False
    ) -> None:
        r = sess.execute(sql)
        self._write_result(io, r, binary=binary, sess=sess)

    def _write_result(
        self, io: P.PacketIO, r, binary: bool = False, sess=None
    ) -> None:
        with FLIGHT.span("wire/write"):
            self._write_packets(io, r, binary, sess)

    def _write_packets(self, io: P.PacketIO, r, binary, sess) -> None:
        if not r.columns:
            io.write_packet(
                P.ok_packet(
                    affected=r.affected,
                    last_insert_id=int(getattr(sess, "last_insert_id", 0)),
                )
            )
            return
        types = getattr(r, "types", None) or [None] * len(r.columns)
        io.write_packet(P.lenenc_int(len(r.columns)))
        for name, t in zip(r.columns, types):
            io.write_packet(P.column_def(name, t))
        io.write_packet(P.eof_packet())
        if binary:
            for row in r.rows:
                io.write_packet(P.binary_row(row, types))
        else:
            for row in r.rows:
                payload = b""
                for v, t in zip(row, types):
                    fv = P.format_value(v, t)
                    payload += b"\xfb" if fv is None else P.lenenc_str(fv)
                io.write_packet(payload)
        io.write_packet(P.eof_packet())
