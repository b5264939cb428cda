"""Layered config system (ref: FilodbSettings.scala:127 — defaults <- file
<- overrides, validated; filodb-defaults.conf `filodb.schemas` declarations)."""
import pytest

from filodb_tpu.config import ConfigError, FilodbSettings
from filodb_tpu.utils import hoconlite
from filodb_tpu.utils.hoconlite import Duration


# ------------------------------------------------------------- hocon-lite

def test_hocon_basic_types_and_nesting():
    cfg = hoconlite.loads("""
    // top comment
    filodb {
      spread_default = 2          # inline comment
      query {
        sample_limit = 500000
        faster_rate = off
      }
      store.flush_interval_ms = 1h
      tags = [a, "b c", 3]
    }
    """)
    f = cfg["filodb"]
    assert f["spread_default"] == 2
    assert f["query"]["sample_limit"] == 500_000
    assert f["query"]["faster_rate"] is False
    assert f["store"]["flush_interval_ms"] == Duration(3_600_000.0)
    assert f["tags"] == ["a", "b c", 3]


def test_hocon_duplicate_blocks_merge_later_wins():
    cfg = hoconlite.loads("""
    a {
      x = 1
      y = 2
    }
    a.x = 9
    """)
    assert cfg["a"] == {"x": 9, "y": 2}


def test_hocon_durations():
    cfg = hoconlite.loads("t1 = 500ms\nt2 = 5 seconds\nt3 = 2 hours")
    assert cfg["t1"].millis == 500
    assert cfg["t2"].seconds == 5
    assert cfg["t3"].millis == 2 * 3_600_000


def test_hocon_errors():
    with pytest.raises(hoconlite.HoconError):
        hoconlite.loads("a {\n b = 1")
    with pytest.raises(hoconlite.HoconError):
        hoconlite.loads("}")


# ---------------------------------------------------------------- layering

def test_file_layer_hocon(tmp_path):
    p = tmp_path / "filodb.conf"
    p.write_text("""
    filodb {
      spread_default = 3
      query.sample_limit = 42
      store.flush_interval_ms = 30 minutes
    }
    """)
    s = FilodbSettings.load(str(p), env={})
    assert s.spread_default == 3
    assert s.query.sample_limit == 42
    assert s.store.flush_interval_ms == 30 * 60 * 1000
    # untouched defaults remain
    assert s.store.groups_per_shard == 60


def test_env_layer_overrides_file(tmp_path):
    p = tmp_path / "filodb.conf"
    p.write_text("filodb.query.sample_limit = 42")
    s = FilodbSettings.load(str(p), env={
        "FILODB_QUERY_SAMPLE_LIMIT": "77",
        "FILODB_STORE_DEVICE_MIRROR_ENABLED": "false",
        "FILODB_SPREAD_DEFAULT": "4",
    })
    assert s.query.sample_limit == 77
    assert s.store.device_mirror_enabled is False
    assert s.spread_default == 4


def test_env_durations_and_foreign_vars():
    s = FilodbSettings.load(None, env={
        "FILODB_STORE_FLUSH_INTERVAL_MS": "30 minutes",
        "FILODB_KAFKA_IT": "1",               # sibling tool's var: ignored
        "FILODB_TPU_CONFIG": "/nonexistent",  # the pointer itself: ignored
    })
    assert s.store.flush_interval_ms == 30 * 60 * 1000
    # typos inside the query_/store_ namespaces still raise
    with pytest.raises(ConfigError):
        FilodbSettings.load(None, env={"FILODB_QUERY_SAMPLE_LIMITT": "5"})


def test_partition_schema_top_level_typo_raises():
    with pytest.raises(ConfigError, match="optionz"):
        FilodbSettings().overlay(
            {"partition_schema": {"optionz": {"metric_column": "m"}}})


def test_spread_assignment_hocon_gives_config_error():
    with pytest.raises(ConfigError, match="spread_assignment"):
        FilodbSettings().overlay({"spread_assignment": ["{ garbled }"]})


def test_config_schemas_flow_into_memstore():
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    s = FilodbSettings().overlay({"schemas": {
        "env-schema": {"columns": ["timestamp:ts", "v:double"],
                       "value_column": "v"}}})
    ms = TimeSeriesMemStore(config=s)
    assert "env-schema" in ms.schemas      # no per-call-site plumbing


def test_unknown_key_raises_with_path(tmp_path):
    p = tmp_path / "filodb.conf"
    p.write_text("filodb.query.sample_limitt = 42")
    with pytest.raises(ConfigError, match="sample_limitt"):
        FilodbSettings.load(str(p), env={})


def test_type_validation():
    with pytest.raises(ConfigError, match="boolean"):
        FilodbSettings().overlay({"query": {"faster_rate": "maybe"}})
    with pytest.raises(ConfigError, match="integer"):
        FilodbSettings().overlay({"query": {"sample_limit": 1.5}})
    with pytest.raises(ConfigError, match="non-duration"):
        FilodbSettings().overlay({"query": {"sample_limit": Duration(5.0)}})


# ------------------------------------------------------- declared schemas

SCHEMA_CONF = """
filodb {
  schemas {
    temp-sensor {
      columns = ["timestamp:ts", "reading:double", "errors:double:detect_drops"]
      value_column = reading
    }
  }
  partition_schema.options.shard_key_columns = [_ws_, _ns_, _metric_]
}
"""


def test_config_declared_schema(tmp_path):
    p = tmp_path / "filodb.conf"
    p.write_text(SCHEMA_CONF)
    s = FilodbSettings.load(str(p), env={})
    assert s.schemas is not None
    sch = s.schemas["temp-sensor"]
    assert sch.value_column == "reading"
    assert sch.column("errors").detect_drops
    # built-ins still present
    assert "prom-counter" in s.schemas


def test_config_declared_schema_is_usable(tmp_path):
    """A config-declared schema must flow into a working server."""
    import numpy as np
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.query.engine import QueryEngine
    p = tmp_path / "filodb.conf"
    p.write_text(SCHEMA_CONF)
    s = FilodbSettings.load(str(p), env={})
    ms = TimeSeriesMemStore(schemas=s.schemas)
    ms.setup("prometheus", 0)
    START = 1_600_000_000_000
    keys = [PartKey.make("room_temp", {"_ws_": "w", "_ns_": "n",
                                       "instance": f"i{i}"}) for i in range(3)]
    n = 60
    batch = RecordBatch(
        s.schemas["temp-sensor"], keys,
        np.repeat(np.arange(3, dtype=np.int32), n),
        np.tile(START + np.arange(n, dtype=np.int64) * 10_000, 3),
        {"reading": np.arange(3 * n, dtype=np.float64),
         "errors": np.zeros(3 * n)})
    ms.ingest("prometheus", 0, batch, offset=1)
    eng = QueryEngine("prometheus", ms)
    res = eng.query_range('sum(room_temp)', START // 1000 + 60, 60,
                          START // 1000 + 500)
    assert res.error is None, res.error
    assert len(list(res.series())) == 1


@pytest.mark.parametrize("bad,msg", [
    ({"schemas": {"x": {"columns": ["t:ts"], "value_column": "nope"}}},
     "value_column"),
    ({"schemas": {"x": {"columns": ["v:double"], "value_column": "v"}}},
     "first column"),
    ({"schemas": {"x": {"columns": ["t:ts", "v:blob"],
                        "value_column": "v"}}}, "name:type"),
    ({"schemas": {"x": {"columns": ["t:ts", "v:double:bogus"],
                        "value_column": "v"}}}, "unknown flags"),
    ({"schemas": {"x": {"columns": ["t:ts", "v:double"], "value_column": "v",
                        "downsample_schema": "ghost"}}}, "not defined"),
])
def test_schema_validation_errors(bad, msg):
    with pytest.raises(ConfigError, match=msg):
        FilodbSettings().overlay(bad)
