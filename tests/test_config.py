"""Config parser tests — one per rule in reference config.cpp:53-143."""

import pytest

from p2p_gossipprotocol_tpu.config import (
    ConfigError, NetworkConfig, NodeInfo, _stoi, is_valid_ip, is_valid_port,
)


def write(tmp_path, text):
    p = tmp_path / "network.txt"
    p.write_text(text)
    return str(p)


def test_reference_sample_config(tmp_path):
    # 20 seeds as in reference network.txt:1-20.
    lines = [f"192.168.1.{100 + i}:{8000 + i}" for i in range(20)]
    cfg = NetworkConfig(write(tmp_path, "\n".join(lines)))
    assert len(cfg.get_seed_nodes()) == 20
    assert cfg.get_seed_nodes()[0] == NodeInfo("192.168.1.100", 8000)
    # Quorum n//2+1 (config.cpp:76).
    assert cfg.get_min_required_seeds() == 11
    # Defaults (config.cpp:31-39).
    assert cfg.get_ping_interval() == 13
    assert cfg.get_message_interval() == 5
    assert cfg.get_max_messages() == 10
    assert cfg.get_max_missed_pings() == 3
    assert cfg.get_local_ip() == "192.168.99.96"
    assert cfg.get_local_port() == 5000


def test_comments_and_blank_lines_skipped(tmp_path):
    cfg = NetworkConfig(write(
        tmp_path, "# comment\n\n   \n10.0.0.1:9000\n  # indented comment\n"))
    assert cfg.get_seed_nodes() == [NodeInfo("10.0.0.1", 9000)]
    assert cfg.get_min_required_seeds() == 1


def test_key_value_params_parsed_and_plumbed(tmp_path):
    cfg = NetworkConfig(write(tmp_path, (
        "ping_interval = 7\nmessage_interval=2\nmax_messages = 4\n"
        "max_missed_pings=5\n10.0.0.1:9000\n")))
    assert cfg.get_ping_interval() == 7
    assert cfg.get_message_interval() == 2
    assert cfg.get_max_messages() == 4
    assert cfg.get_max_missed_pings() == 5


def test_unknown_keys_silently_ignored(tmp_path):
    # config.cpp:93-96 has no else-clause for unknown keys.
    cfg = NetworkConfig(write(tmp_path, "frobnicate=yes\n10.0.0.1:9000\n"))
    assert len(cfg.get_seed_nodes()) == 1


def test_empty_key_or_value_rejected_with_line_number(tmp_path):
    with pytest.raises(ConfigError, match="Error at line 1"):
        NetworkConfig(write(tmp_path, "=5\n10.0.0.1:9000\n"))
    with pytest.raises(ConfigError, match="Invalid configuration format"):
        NetworkConfig(write(tmp_path, "ping_interval=\n10.0.0.1:9000\n"))


def test_invalid_ip_rejected(tmp_path):
    with pytest.raises(ConfigError, match="Invalid IP address"):
        NetworkConfig(write(tmp_path, "999.0.0.1:9000\n"))
    with pytest.raises(ConfigError, match="Invalid IP address"):
        NetworkConfig(write(tmp_path, "not-an-ip:9000\n"))


def test_invalid_port_rejected(tmp_path):
    with pytest.raises(ConfigError, match="Invalid port number"):
        NetworkConfig(write(tmp_path, "10.0.0.1:0\n"))
    with pytest.raises(ConfigError, match="Invalid port number"):
        NetworkConfig(write(tmp_path, "10.0.0.1:70000\n"))
    with pytest.raises(ConfigError, match="Invalid port format"):
        NetworkConfig(write(tmp_path, "10.0.0.1:abc\n"))


def test_missing_colon_rejected(tmp_path):
    with pytest.raises(ConfigError, match="Invalid seed node format"):
        NetworkConfig(write(tmp_path, "10.0.0.1\n"))


def test_no_seeds_rejected(tmp_path):
    with pytest.raises(ConfigError, match="No valid seed nodes"):
        NetworkConfig(write(tmp_path, "# only comments\nping_interval=5\n"))


def test_missing_file_rejected(tmp_path):
    with pytest.raises(ConfigError, match="Unable to open config file"):
        NetworkConfig(str(tmp_path / "nope.txt"))


def test_nonpositive_params_rejected(tmp_path):
    # config.cpp:122-126
    for k in ("ping_interval", "message_interval", "max_messages",
              "max_missed_pings"):
        with pytest.raises(ConfigError, match="must be positive"):
            NetworkConfig(write(tmp_path, f"{k}=0\n10.0.0.1:9000\n"))
        with pytest.raises(ConfigError, match="must be positive"):
            NetworkConfig(write(tmp_path, f"{k}=-3\n10.0.0.1:9000\n"))


def test_duplicate_seeds_rejected(tmp_path):
    # config.cpp:134-142
    with pytest.raises(ConfigError, match="Duplicate seed nodes"):
        NetworkConfig(write(tmp_path, "10.0.0.1:9000\n10.0.0.1:9000\n"))
    # Same ip different port is fine.
    cfg = NetworkConfig(write(tmp_path, "10.0.0.1:9000\n10.0.0.1:9001\n"))
    assert cfg.get_min_required_seeds() == 2


def test_local_address_keys_new(tmp_path):
    # Fixes the reference's hard-coded local address (config.cpp:38-39).
    cfg = NetworkConfig(write(
        tmp_path, "local_ip=127.0.0.1\nlocal_port=6001\n10.0.0.1:9000\n"))
    assert cfg.get_local_ip() == "127.0.0.1"
    assert cfg.get_local_port() == 6001


def test_sim_keys(tmp_path):
    cfg = NetworkConfig(write(tmp_path, (
        "backend=jax\ngraph=er\nmode=pushpull\nn_peers=10000\n"
        "n_messages=16\nchurn_rate=0.05\nbyzantine_fraction=0.1\n"
        "er_p=0.001\nprng_seed=42\n10.0.0.1:9000\n")))
    assert cfg.backend == "jax"
    assert cfg.graph == "er"
    assert cfg.mode == "pushpull"
    assert cfg.n_peers == 10000
    assert cfg.churn_rate == 0.05
    assert cfg.prng_seed == 42


def test_bad_sim_values_rejected(tmp_path):
    with pytest.raises(ConfigError, match="Unknown backend"):
        NetworkConfig(write(tmp_path, "backend=cuda\n10.0.0.1:9000\n"))
    with pytest.raises(ConfigError, match="Unknown graph model"):
        NetworkConfig(write(tmp_path, "graph=torus\n10.0.0.1:9000\n"))
    with pytest.raises(ConfigError, match="Unknown gossip mode"):
        NetworkConfig(write(tmp_path, "mode=yell\n10.0.0.1:9000\n"))
    with pytest.raises(ConfigError, match="churn_rate"):
        NetworkConfig(write(tmp_path, "churn_rate=1.5\n10.0.0.1:9000\n"))


def test_get_random_seeds(tmp_path):
    lines = "\n".join(f"10.0.0.{i}:9000" for i in range(1, 11))
    cfg = NetworkConfig(write(tmp_path, lines))
    sel = cfg.get_random_seeds(5)
    assert len(sel) == 5
    assert len(set(sel)) == 5
    assert all(s in cfg.get_seed_nodes() for s in sel)
    with pytest.raises(ConfigError, match="more seeds than available"):
        cfg.get_random_seeds(11)


def test_to_string_shape(tmp_path):
    cfg = NetworkConfig(write(tmp_path, "10.0.0.1:9000\n"))
    s = cfg.to_string()
    assert "Network Configuration:" in s
    assert "Seed Nodes (1):" in s
    assert "Minimum Required Seeds: 1" in s
    assert "Ping Interval: 13 seconds" in s


def test_stoi_semantics():
    # std::stoi parses leading digits, ignores trailing junk.
    assert _stoi("42") == 42
    assert _stoi(" 42abc") == 42
    assert _stoi("-7") == -7
    with pytest.raises(ValueError):
        _stoi("abc")


def test_ip_port_validators():
    assert is_valid_ip("192.168.1.1")
    assert not is_valid_ip("192.168.1")
    assert not is_valid_ip("192.168.1.256")
    assert is_valid_port(1) and is_valid_port(65535)
    assert not is_valid_port(0) and not is_valid_port(65536)


def test_non_numeric_int_values_raise_config_error(tmp_path):
    # Review finding: stoi failures must surface as line-numbered ConfigError.
    with pytest.raises(ConfigError, match="Error at line 1: Invalid value"):
        NetworkConfig(write(tmp_path, "ping_interval=fast\n10.0.0.1:9000\n"))


def test_local_address_validated(tmp_path):
    with pytest.raises(ConfigError, match="Invalid local_ip"):
        NetworkConfig(write(tmp_path, "local_ip=banana\n10.0.0.1:9000\n"))
    with pytest.raises(ConfigError, match="Invalid local_port"):
        NetworkConfig(write(tmp_path, "local_port=70000\n10.0.0.1:9000\n"))


def test_negative_sim_ints_rejected(tmp_path):
    with pytest.raises(ConfigError, match="must be non-negative"):
        NetworkConfig(write(tmp_path, "n_peers=-5\n10.0.0.1:9000\n"))


def test_engine_key(tmp_path):
    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\nengine=aligned\n")
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    assert NetworkConfig(str(cfg)).engine == "aligned"


def test_engine_key_default_and_invalid(tmp_path):
    import pytest
    from p2p_gossipprotocol_tpu.config import ConfigError, NetworkConfig
    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\n")
    assert NetworkConfig(str(cfg)).engine == "edges"
    cfg.write_text("10.0.0.1:8000\nengine=warp\n")
    with pytest.raises(ConfigError, match="Unknown engine"):
        NetworkConfig(str(cfg))


def test_roll_groups_key(tmp_path):
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    cfg = tmp_path / "net.txt"
    cfg.write_text("10.0.0.1:8000\nroll_groups=8\n")
    assert NetworkConfig(str(cfg)).roll_groups == 8
    cfg.write_text("10.0.0.1:8000\nroll_groups=0\n")
    assert NetworkConfig(str(cfg)).roll_groups == 0
    # measured-best DEFAULTS (round-5 on-chip A/Bs): grouped rolls +
    # windowed pull on; from_config degrades pull_window when a
    # scenario can't support it
    cfg.write_text("10.0.0.1:8000\n")
    parsed = NetworkConfig(str(cfg))
    assert parsed.roll_groups == 4 and parsed.pull_window == 1


def test_config_parser_never_crashes_on_junk(tmp_path):
    """Seeded fuzz: any byte soup must either parse or raise ConfigError
    with a line number — never an unhandled exception (the reference
    atoi-crashes on non-numeric values, SURVEY §2-C3)."""
    import random

    from p2p_gossipprotocol_tpu.config import ConfigError, NetworkConfig

    rng = random.Random(0)
    tokens = ["10.0.0.1:8000", "=", ":", "#", "n_peers", "mode", "push",
              "999999999999999999999", "-1", "1e9", "::", "a.b.c.d:x",
              "backend", "jax", "\x00", "🦜", " ", "\t", "engine"]
    cfg = tmp_path / "net.txt"
    for i in range(200):
        lines = ["10.0.0.1:8000"] if rng.random() < 0.5 else []
        for _ in range(rng.randrange(6)):
            lines.append("".join(rng.choice(tokens)
                                 for _ in range(rng.randrange(1, 5))))
        cfg.write_text("\n".join(lines), errors="replace")
        try:
            NetworkConfig(str(cfg))
        except ConfigError:
            pass


def test_examples_scale_config_selects_the_measured_best_layout():
    """examples/scale.txt (the scale-engine showcase) parses and routes
    onto the aligned engine with the round-5 features on — the example
    must never rot."""
    from p2p_gossipprotocol_tpu.config import NetworkConfig
    from p2p_gossipprotocol_tpu.engines import build_simulator

    cfg = NetworkConfig("/root/repo/examples/scale.txt")
    assert (cfg.engine, cfg.mode) == ("aligned", "pushpull")
    # measured-best layout (docs/PERFORMANCE.md): windowed pull on a
    # roll-grouped overlay; fuse_update/block_perm off at this width
    assert cfg.pull_window == 1 and cfg.message_stagger == 1
    assert cfg.roll_groups == 4 and not cfg.fuse_update
    # cheap instantiation: shrink the peer count, keep every knob
    sim, engine = build_simulator(cfg, n_peers=4096)
    assert engine == "aligned"
    assert sim.pull_window and sim.topo.roll_groups == 4
    assert sim.message_stagger == 1
    assert sim.liveness_every == 3          # 13 s / 5 s


def test_supervise_keys_parse_and_validate(tmp_path):
    cfg = NetworkConfig(write(
        tmp_path, "10.0.0.1:9000\nsupervise=1\nsupervise_workers=4\n"
        "supervise_devs_per_proc=2\nsupervise_spmd=chief\n"
        "supervise_grace_s=30\nsupervise_deadline_s=5\n"
        "supervise_min_workers=2\n"))
    assert cfg.supervise == 1
    assert cfg.supervise_workers == 4
    assert cfg.supervise_devs_per_proc == 2
    assert cfg.supervise_spmd == "chief"
    assert cfg.supervise_grace_s == 30.0
    assert cfg.supervise_deadline_s == 5.0
    assert cfg.supervise_min_workers == 2


def test_supervise_bad_values_are_named_errors(tmp_path):
    with pytest.raises(ConfigError, match="supervise_spmd"):
        NetworkConfig(write(
            tmp_path, "10.0.0.1:9000\nsupervise_spmd=quorum\n"))
    with pytest.raises(ConfigError, match="supervise_min_workers"):
        NetworkConfig(write(
            tmp_path, "10.0.0.1:9000\nsupervise=1\n"
            "supervise_workers=2\nsupervise_min_workers=3\n"))
    with pytest.raises(ConfigError, match="non-negative"):
        NetworkConfig(write(
            tmp_path, "10.0.0.1:9000\nsupervise_grace_s=-1\n"))


def test_config_accepts_auto_spellings(tmp_path):
    cfg = NetworkConfig(write(
        tmp_path, "127.0.0.1:8000\nbackend=jax\nn_peers=256\n"
        "serve_chunk=-1\nfrontier_threshold=-1\n"))
    assert cfg.serve_chunk == -1 and cfg.frontier_threshold == -1.0
    for bad in ("serve_chunk=0", "frontier_threshold=0",
                "frontier_threshold=1.5"):
        with pytest.raises(ConfigError):
            NetworkConfig(write(
                tmp_path, f"127.0.0.1:8000\nbackend=jax\nn_peers=256\n"
                f"{bad}\n"))
