"""Config system + server binary bootstrap/shutdown.

Reference: pkg/config/config.go TOML layering with cmd/tidb-server flag
overrides (main.go:200-262, overrideConfig) and graceful shutdown with
storage persistence (main.go:330-341).
"""

import os
import signal
import socket
import struct
import subprocess
import sys
import time

import pytest

from tidb_tpu.utils.config import Config


class TestConfigLayers:
    def test_defaults(self):
        c = Config()
        assert c.port == 4000 and c.host == "127.0.0.1" and c.store == "tpu"

    def test_from_toml_and_override(self, tmp_path):
        f = tmp_path / "c.toml"
        f.write_text(
            'port = 4407\nhost = "0.0.0.0"\n'
            "[variables]\ntidb_slow_log_threshold = 5\n"
        )
        c = Config.from_toml(str(f))
        assert c.port == 4407 and c.host == "0.0.0.0"
        assert c.variables == {"tidb_slow_log_threshold": 5}
        # CLI layer wins where set, file value survives elsewhere
        c2 = c.override(port=4500, host=None)
        assert c2.port == 4500 and c2.host == "0.0.0.0"

    def test_unknown_key_rejected(self, tmp_path):
        f = tmp_path / "c.toml"
        f.write_text("prot = 1\n")
        with pytest.raises(ValueError):
            Config.from_toml(str(f))

    def test_variables_seed_globals(self):
        from tidb_tpu.session.session import Session
        from tidb_tpu.storage import Catalog

        cat = Catalog()
        cat.global_sysvars = {}
        Config(variables={"tidb_slow_log_threshold": 7}).apply_variables(cat)
        s = Session(catalog=cat)
        assert int(s.vars.get("tidb_slow_log_threshold")) == 7


def _wire_query(port, sql):
    from tidb_tpu.server import protocol as P

    sock = socket.create_connection(("127.0.0.1", port), timeout=10)
    io = P.PacketIO(sock)
    io.read_packet()  # greeting (root/empty password)
    caps = P.CLIENT_PROTOCOL_41 | P.CLIENT_SECURE_CONNECTION
    body = struct.pack("<I", caps) + struct.pack("<I", 1 << 24)
    body += bytes([0xFF]) + b"\x00" * 23 + b"root\x00" + bytes([0])
    io.write_packet(body)
    assert io.read_packet()[0] == 0x00
    io.reset_seq()
    io.write_packet(b"\x03" + sql.encode())
    first = io.read_packet()
    rows = []
    if first[0] not in (0x00, 0xFF):
        ncols = first[0]
        for _ in range(ncols):
            io.read_packet()
        io.read_packet()  # EOF
        while True:
            p = io.read_packet()
            if p[0] == 0xFE and len(p) < 9:
                break
            rows.append(p)
    sock.close()
    return first, rows


def test_server_binary_persistence_roundtrip(tmp_path):
    """Boot with --config + --path, write data over the wire, SIGTERM,
    boot again, data survives."""
    cfgf = tmp_path / "server.toml"
    cfgf.write_text("port = 0\n")  # ephemeral; but we need the port...
    datadir = tmp_path / "data"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    port = _free_port()

    def boot():
        return subprocess.Popen(
            [
                sys.executable, "tidb_server.py",
                "--config", str(cfgf), "--port", str(port),
                "--path", str(datadir),
            ],
            cwd="/root/repo", env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

    proc = boot()
    try:
        _wait_port(port)
        _wire_query(port, "create table cfg_t (a int)")
        _wire_query(port, "insert into cfg_t values (11),(22)")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
        assert (datadir / "manifest.json").exists()

        proc = boot()
        _wait_port(port)
        first, rows = _wire_query(port, "select a from cfg_t order by a")
        assert len(rows) == 2
    finally:
        proc.kill()
        proc.wait(timeout=30)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _wait_port(port, timeout=120):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            socket.create_connection(("127.0.0.1", port), timeout=1).close()
            return
        except OSError:
            time.sleep(0.5)
    raise TimeoutError(f"server on :{port} never came up")
