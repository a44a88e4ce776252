import json

from symbiont_tpu.config import SymbiontConfig, load_config


def test_defaults():
    cfg = SymbiontConfig()
    assert cfg.vector_store.dim == 768
    assert cfg.vector_store.collection == "symbiont_document_embeddings"
    assert cfg.engine.length_buckets == [32, 64, 128, 256, 512]


def test_file_then_env_precedence(tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps({"api": {"port": 9000}, "engine": {"embedding_dim": 384}}))
    cfg = load_config(p, env={"SYMBIONT_API_PORT": "9100"})
    assert cfg.api.port == 9100  # env wins over file
    assert cfg.engine.embedding_dim == 384  # file wins over default


def test_reference_env_aliases(tmp_path):
    cfg = load_config(env={
        "NATS_URL": "symbus://bus:4233",
        "FORCE_CPU": "true",  # NOT an alias: JAX_PLATFORMS chooses the device
        "API_SERVER_PORT": "8088",
    })
    assert cfg.bus.url == "symbus://bus:4233"
    assert not hasattr(cfg.engine, "force_cpu")
    assert cfg.api.port == 8088


def test_canonical_env_beats_legacy_alias():
    cfg = load_config(env={
        "NATS_URL": "nats://old-host:4222",
        "SYMBIONT_BUS_URL": "symbus://bus:4233",
    })
    assert cfg.bus.url == "symbus://bus:4233"


def test_explicit_missing_config_path_raises(tmp_path):
    import pytest

    with pytest.raises(FileNotFoundError):
        load_config(tmp_path / "missing.json")


def test_unknown_file_key_rejected(tmp_path):
    p = tmp_path / "cfg.json"
    p.write_text(json.dumps({"api": {"bogus": 1}}))
    try:
        load_config(p)
    except ValueError as e:
        assert "bogus" in str(e)
    else:
        raise AssertionError("expected ValueError")


def test_fused_top_k_must_be_covered_by_warm_buckets():
    """api.fused_search_max_top_k above vector_store.warm_top_k would send
    fused queries into unwarmed k buckets (cold compile inside the probe
    timeout) — rejected at startup."""
    import pytest

    from symbiont_tpu.config import ApiConfig, SymbiontConfig, VectorStoreConfig

    with pytest.raises(ValueError, match="warm_top_k"):
        SymbiontConfig(api=ApiConfig(fused_search_max_top_k=64))
    SymbiontConfig(api=ApiConfig(fused_search_max_top_k=64),
                   vector_store=VectorStoreConfig(warm_top_k=64))


def test_validators_fire_on_loaded_overrides():
    """File/env overrides mutate sections via setattr, bypassing dataclass
    construction — load_config must re-run the validators afterwards."""
    import pytest

    from symbiont_tpu.config import load_config

    with pytest.raises(ValueError, match="warm_top_k"):
        load_config(env={"SYMBIONT_API_FUSED_SEARCH_MAX_TOP_K": "64"})
    with pytest.raises(ValueError, match="stream_chunk"):
        load_config(env={"SYMBIONT_LM_STREAM_CHUNK": "24"})
    load_config(env={"SYMBIONT_API_FUSED_SEARCH_MAX_TOP_K": "64",
                     "SYMBIONT_VECTOR_STORE_WARM_TOP_K": "64"})
