"""Ecosystem tools: BACKUP/RESTORE (BR), IMPORT INTO (lightning), and
the dumpling-style logical export.

Reference: br/pkg/task/{backup,restore}.go with checkpoints
(br/pkg/checkpoint/backup.go), pkg/disttask/importinto, dumpling/export.
"""

import os
import subprocess
import sys

import pytest

from tidb_tpu.session.session import Session
from tidb_tpu.tools.dump import dump_database
from tidb_tpu.utils import failpoint


@pytest.fixture()
def sess():
    s = Session()
    s.execute("create database app")
    s.execute(
        "create table app.t (id int primary key auto_increment, "
        "v varchar(8), ts datetime)"
    )
    s.execute(
        "insert into app.t (v, ts) values "
        "('a','2024-01-01 10:00:00'),('b','2024-02-02 11:30:45')"
    )
    return s


def test_backup_restore_single_db(sess, tmp_path):
    sess.execute("create table other (x int)")
    sess.execute(f"backup database app to '{tmp_path / 'br'}'")
    s2 = Session()
    s2.execute(f"restore database app from '{tmp_path / 'br'}'")
    assert s2.execute("select id, v from app.t order by id").rows == [
        (1, "a"), (2, "b"),
    ]
    assert not s2.catalog.has_table("test", "other")
    # schema extras survive: PK + autoinc keep allocating after restore
    s2.execute("insert into app.t (v, ts) values ('c', null)")
    assert s2.execute("select max(id) from app.t").rows == [(3,)]


def test_backup_all_databases(sess, tmp_path):
    sess.execute("create table other (x int)")
    sess.execute("insert into other values (9)")
    sess.execute(f"backup database * to '{tmp_path / 'br'}'")
    s2 = Session()
    s2.execute(f"restore database * from '{tmp_path / 'br'}'")
    assert s2.execute("select x from other").rows == [(9,)]
    assert s2.execute("select count(*) from app.t").rows == [(2,)]


def test_backup_checkpoint_resume(sess, tmp_path):
    """An interrupted backup resumes from the checkpoint ledger and
    skips completed tables (br/pkg/checkpoint/backup.go)."""
    sess.execute("create table app.u (x int)")
    sess.execute("insert into app.u values (1)")
    path = str(tmp_path / "br")
    calls = [0]

    def boom():
        calls[0] += 1
        if calls[0] == 2:
            raise RuntimeError("simulated crash mid-backup")

    failpoint.enable("persist/backup-table", boom)
    try:
        with pytest.raises(RuntimeError):
            sess.execute(f"backup database app to '{path}'")
    finally:
        failpoint.disable("persist/backup-table")
    assert os.path.exists(os.path.join(path, "checkpoint.json"))
    # resume: completes without rewriting the checkpointed first table
    from tidb_tpu.storage.persist import save_catalog

    written = save_catalog(sess.catalog, path, dbs=["app"], resume=True)
    assert written == 1  # only the table the crash interrupted
    assert not os.path.exists(os.path.join(path, "checkpoint.json"))
    s2 = Session()
    s2.execute(f"restore database app from '{path}'")
    assert s2.execute("select count(*) from app.t").rows == [(2,)]
    assert s2.execute("select x from app.u").rows == [(1,)]


def test_import_into_statement(sess, tmp_path):
    f = tmp_path / "rows.tsv"
    with open(f, "w") as fh:
        for i in range(1000):
            fh.write(f"{i}\tz{i % 3}\n")
    sess.execute("create table app.big (id int, v varchar(8))")
    r = sess.execute(f"import into app.big from '{f}'")
    assert r.affected == 1000
    assert sess.execute("select count(*), sum(id) from app.big").rows == [
        (1000, 499500)
    ]


def test_import_into_custom_separator(sess, tmp_path):
    f = tmp_path / "rows.csv"
    f.write_text("1,a\n2,b\n")
    sess.execute("create table app.c (id int, v varchar(4))")
    sess.execute(f"import into app.c from '{f}' fields terminated by ','")
    assert sess.execute("select * from app.c order by id").rows == [
        (1, "a"), (2, "b"),
    ]


def test_dump_sql_roundtrip(sess, tmp_path):
    out = str(tmp_path / "dump")
    counts = dump_database(sess.catalog, "app", out, fmt="sql")
    assert counts == {"t": 2}
    s3 = Session()
    s3.execute("create database app")
    s3.db = "app"
    for stmt in open(os.path.join(out, "app.t.sql")).read().split(";\n"):
        if stmt.strip():
            s3.execute(stmt)
    assert s3.execute("select id, v from app.t order by id").rows == [
        (1, "a"), (2, "b"),
    ]
    # schema round-trips the auto_increment attribute
    assert s3.catalog.table("app", "t").autoinc_col == "id"


def test_dump_csv(sess, tmp_path):
    out = str(tmp_path / "dumpcsv")
    counts = dump_database(sess.catalog, "app", out, fmt="csv")
    assert counts == {"t": 2}
    lines = open(os.path.join(out, "app.t.csv")).read().strip().splitlines()
    assert lines[0] == "id,v,ts"
    assert len(lines) == 3


def test_dump_cli(sess, tmp_path):
    sess.execute(f"backup database app to '{tmp_path / 'br'}'")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [
            sys.executable, "-m", "tidb_tpu.tools.dump",
            "--snapshot", str(tmp_path / "br"),
            "--db", "app", "--out", str(tmp_path / "out"),
        ],
        capture_output=True, text=True, cwd="/root/repo", env=env,
    )
    assert out.returncode == 0 and "app.t: 2 rows" in out.stdout


def test_backup_requires_super(sess, tmp_path):
    sess.execute("create user pleb")
    pleb = Session(catalog=sess.catalog, user="pleb")
    with pytest.raises(PermissionError):
        pleb.execute(f"backup database app to '{tmp_path / 'x'}'")
