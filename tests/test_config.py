"""Config layering tests (reference analog: libs/core/ini tests)."""

import pytest

from hpx_tpu.core.config import Configuration, _parse_ini_text
from hpx_tpu.core.errors import BadParameter


def test_defaults_present():
    cfg = Configuration(environ={})
    assert cfg.get("hpx.localities") == "1"
    assert cfg.get_int("hpx.parcel.port") == 7910
    assert cfg.get_bool("hpx.parcel.enable")


def test_ini_parse_sections():
    data = _parse_ini_text(
        """
        ; comment
        [hpx.parcel]
        port = 1234
        address=10.0.0.1
        [hpx]
        localities = 4
        """
    )
    assert data["hpx.parcel.port"] == "1234"
    assert data["hpx.parcel.address"] == "10.0.0.1"
    assert data["hpx.localities"] == "4"


def test_env_overlay():
    cfg = Configuration(environ={"HPX_TPU_PARCEL__PORT": "9999"})
    assert cfg.get_int("hpx.parcel.port") == 9999


def test_cli_overlay_and_remaining():
    cfg = Configuration(
        argv=["prog", "--hpx:threads=4", "--hpx:ini=hpx.queuing=static",
              "--user-arg", "--hpx:dump-config"],
        environ={},
    )
    assert cfg.os_threads() == 4
    assert cfg.get("hpx.queuing") == "static"
    assert cfg.get_bool("hpx.diagnostics.dump_config")
    assert cfg.remaining_argv == ["prog", "--user-arg"]


def test_cli_layer_beats_env():
    cfg = Configuration(
        argv=["--hpx:ini=hpx.parcel.port=42"],
        environ={"HPX_TPU_PARCEL__PORT": "9999"},
    )
    assert cfg.get_int("hpx.parcel.port") == 42


def test_unknown_hpx_flag_raises():
    with pytest.raises(BadParameter):
        Configuration(argv=["--hpx:bogus=1"], environ={})


def test_programmatic_override_wins():
    cfg = Configuration(environ={}, overrides={"hpx.localities": 8})
    assert cfg.get_int("hpx.localities") == 8


def test_section_query_and_dump():
    cfg = Configuration(environ={})
    sec = cfg.section("hpx.parcel")
    assert "port" in sec and "enable" in sec
    assert "hpx.parcel.port = 7910" in cfg.dump()


def test_strict_mode_rejects_undeclared_keys():
    cfg = Configuration(environ={}, strict=True)
    with pytest.raises(BadParameter, match="undeclared"):
        cfg.set("hpx.cache.kv_dytpe", "int8")   # transposed typo
    with pytest.raises(BadParameter, match="undeclared"):
        cfg.get("hpx.serving.paged_kernal")
    # non-hpx keys are application-private, never policed
    cfg.set("myapp.anything", "1")
    assert Configuration(environ={}).get("hpx.nope") is None  # lax: ok


def test_strict_mode_enforces_declared_choices():
    """Enumerated knobs (declared with choices=) reject out-of-set
    values at set() time with the valid set spelled out — a typo'd
    kv_dtype fails HERE, not as a downstream serving error."""
    cfg = Configuration(environ={}, strict=True)
    for ok in ("bf16", "int8", "fp8"):
        cfg.set("hpx.cache.kv_dtype", ok)
    for ok in ("auto", "gather", "fused", "fused_online"):
        cfg.set("hpx.serving.paged_kernel", ok)
    with pytest.raises(BadParameter, match="bf16.*int8.*fp8"):
        cfg.set("hpx.cache.kv_dtype", "fp8_e5m2")
    with pytest.raises(BadParameter, match="fused_online"):
        cfg.set("hpx.serving.paged_kernel", "online")
    # free-form str keys stay free-form under strict
    cfg.set("hpx.logging.destination", "wherever.log")
    # lax mode: choices are documentation, not enforcement
    Configuration(environ={}).set("hpx.cache.kv_dtype", "fp8_e5m2")


def test_strict_mode_reserved_vs_unknown_are_distinct_errors():
    """A typo'd key and a declared-but-reserved key are different
    mistakes: the first needs a schema declaration, the second has no
    runtime reader so the write would be silently ignored. Strict
    set() raises a DISTINCT type for each so callers can tell them
    apart."""
    from hpx_tpu.core.errors import (ReservedConfigKey,
                                     UndeclaredConfigKey)
    cfg = Configuration(environ={}, strict=True)
    with pytest.raises(UndeclaredConfigKey, match="undeclared"):
        cfg.set("hpx.serving.prefil_chunk", "64")    # typo
    with pytest.raises(ReservedConfigKey, match="reserved"):
        cfg.set("hpx.queuing", "static")             # parity-only key
    # both are BadParameter subclasses: existing catch-alls still work
    assert issubclass(UndeclaredConfigKey, BadParameter)
    assert issubclass(ReservedConfigKey, BadParameter)
    # reserved keys still ARRIVE through the ini/CLI layers (reference
    # invocations keep working); only runtime set() is policed
    via_cli = Configuration(argv=["--hpx:queuing=static"], environ={},
                            strict=True)
    assert via_cli.get("hpx.queuing") == "static"
    # lax mode: unchanged (reserved set() stays a no-op-by-convention)
    Configuration(environ={}).set("hpx.queuing", "static")


def test_set_bumps_generation():
    """Every set() bumps the change counter a live server polls to
    re-read its reloadable knobs at the next flush boundary."""
    cfg = Configuration(environ={})
    g0 = cfg.generation()
    cfg.set("hpx.serving.prefill_chunk", "64")
    assert cfg.generation() == g0 + 1
    cfg.set("hpx.serving.max_async_steps", "8")
    assert cfg.generation() == g0 + 2


def test_declare_validates_choices():
    from hpx_tpu.core import config_schema
    with pytest.raises(ValueError, match="choices"):
        config_schema.declare("hpx.test.bogus_choice_key", "str", "c",
                              "default outside its own choices",
                              choices=("a", "b"))
    assert not config_schema.is_declared("hpx.test.bogus_choice_key")
    key = config_schema.lookup("hpx.cache.kv_dtype")
    assert key.choices == ("bf16", "int8", "fp8")
    assert config_schema.lookup("hpx.serving.paged_kernel").choices == \
        ("auto", "gather", "fused", "fused_online")
