"""Multi-process cluster e2e through the operator CLI (reference model:
.travis.yml -- goworld start; test_client -strict; goworld reload;
test_client again; goworld stop).  Real OS processes, real TCP."""

import os
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture()
def rundir(tmp_path):
    disp_port, gate_port = free_port(), free_port()
    cfg = tmp_path / "goworld.ini"
    cfg.write_text(
        f"""
[deployment]
dispatchers = 1
games = 2
gates = 1

[dispatcher1]
host = 127.0.0.1
port = {disp_port}

[game_common]
boot_entity = Player
aoi_backend = cpu
position_sync_interval_ms = 50

[gate1]
host = 127.0.0.1
port = {gate_port}
"""
    )
    yield tmp_path, str(cfg), gate_port
    subprocess.run(
        [sys.executable, "-m", "goworld_tpu.cli", "kill", "-d", str(tmp_path / "run")],
        cwd=REPO, env=_env(), capture_output=True,
    )


def _env():
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def cli(args, timeout=90):
    return subprocess.run(
        [sys.executable, "-m", "goworld_tpu.cli", *args],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=timeout,
    )


def test_cli_start_bots_reload_stop(rundir):
    tmp_path, cfg, gate_port = rundir
    run = str(tmp_path / "run")
    script = os.path.join(REPO, "examples", "unity_demo", "server.py")

    r = cli(["start", "-c", cfg, "-s", script, "-d", run])
    assert r.returncode == 0, f"start failed:\n{r.stdout}\n{r.stderr}"

    r = cli(["status", "-d", run])
    assert r.returncode == 0 and r.stdout.count("RUNNING") == 4, r.stdout

    # strict bots against the live cluster -- enough bots and time for the
    # cross-bot AOI visibility oracle to assert real pairs (the soak keeps
    # the 100x30s reference-CI scale behind GW_SOAK=1; this default-on run
    # is the same gauntlet at small scale)
    import re

    bots = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "test_client.py"),
         "--gate", f"127.0.0.1:{gate_port}", "-N", "16",
         "--duration", "8", "--strict"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=120,
    )
    assert bots.returncode == 0, f"bots failed:\n{bots.stdout}\n{bots.stderr}"
    assert "16/16 bots OK" in bots.stdout
    m = re.search(r"visibility checks: (\d+)", bots.stdout)
    assert m and int(m.group(1)) > 0, \
        "visibility oracle never asserted anything:\n" + bots.stdout

    # hot reload with a client CONNECTED THROUGH IT: its avatar state must
    # survive the freeze/restore (this is what distinguishes reload from a
    # cold restart)
    sys.path.insert(0, REPO)
    from goworld_tpu.client import GameClientConnection

    keeper = GameClientConnection(("127.0.0.1", gate_port))
    assert keeper.wait_for(lambda c: c.player is not None, 30), \
        "boot entity never reached keeper client\n" + _logs(run)
    keeper.call_player("enter_game", "keeper")
    assert keeper.wait_for(
        lambda c: c.player.attrs.get("name") == "keeper", 30
    ), "enter_game attr change never reached keeper client\n" + _logs(run)

    r = cli(["reload", "-c", cfg, "-s", script, "-d", run])
    assert r.returncode == 0, f"reload failed:\n{r.stdout}\n{r.stderr}\n" + _logs(run)

    # the avatar survived the freeze with its attrs; the connection never broke
    keeper.call_player("whoami")
    assert keeper.wait_for(
        lambda c: any(
            ("on_whoami", ("keeper",)) in e.calls for e in c.entities.values()
        ),
        15,
    ), "avatar state lost across reload\n" + _logs(run)
    keeper.close()

    # cluster still serves strict bots after the reload
    bots = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "test_client.py"),
         "--gate", f"127.0.0.1:{gate_port}", "-N", "4",
         "--duration", "3", "--strict"],
        cwd=REPO, env=_env(), capture_output=True, text=True, timeout=90,
    )
    assert bots.returncode == 0, f"post-reload bots failed:\n{bots.stdout}\n{bots.stderr}\n" + _logs(run)

    r = cli(["stop", "-d", run])
    assert r.returncode == 0
    time.sleep(0.5)
    r = cli(["status", "-d", run])
    assert "RUNNING" not in r.stdout


def _logs(run):
    out = []
    for fn in sorted(os.listdir(run)):
        if fn.endswith(".log"):
            out.append(f"--- {fn} ---\n" + open(os.path.join(run, fn)).read()[-3000:])
    return "\n".join(out)


def test_cli_build(rundir):
    tmp_path, cfg, _gate_port = rundir
    script = os.path.join(REPO, "examples", "unity_demo", "server.py")
    r = cli(["build", "-c", cfg, "-s", script])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "build OK" in r.stdout

    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    r = cli(["build", "-s", str(bad)])
    assert r.returncode == 1
    assert "build FAILED" in r.stdout


@pytest.mark.skipif(os.environ.get("GW_SOAK") != "1",
                    reason="set GW_SOAK=1 for the 100-bot soak (reference "
                           "CI scale: .travis.yml:36-46)")
def test_soak_100_bots_reload_under_load(rundir):
    """The reference's CI gauntlet: 100 strict bots for 30 s, a hot reload
    UNDER load (freeze/restore with clients connected), then another 30 s
    run -- all with the cross-bot AOI visibility oracle active."""
    tmp_path, cfg, gate_port = rundir
    run = str(tmp_path / "run")
    script = os.path.join(REPO, "examples", "unity_demo", "server.py")
    r = cli(["start", "-c", cfg, "-s", script, "-d", run])
    assert r.returncode == 0, f"start failed:\n{r.stdout}\n{r.stderr}"

    def bots(duration):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "examples", "test_client.py"),
             "--gate", f"127.0.0.1:{gate_port}", "-N", "100",
             "--duration", str(duration), "--strict"],
            cwd=REPO, env=_env(), capture_output=True, text=True, timeout=300,
        )

    import threading

    first = {}
    t = threading.Thread(target=lambda: first.update(r=bots(30)))
    t.start()
    time.sleep(10)  # bots are mid-run: reload NOW (freeze/restore under load)
    rr = cli(["reload", "-c", cfg, "-s", script, "-d", run], timeout=120)
    t.join(300)
    assert rr.returncode == 0, f"reload failed:\n{rr.stdout}\n{rr.stderr}"
    import re

    def vis_checks(stdout):
        m = re.search(r"visibility checks: (\d+)", stdout)
        return int(m.group(1)) if m else 0

    out = first["r"]
    assert out.returncode == 0, f"bots failed:\n{out.stdout}\n{out.stderr}"
    assert "100/100 bots OK" in out.stdout
    assert vis_checks(out.stdout) > 0, \
        "visibility oracle never asserted anything:\n" + out.stdout
    out2 = bots(30)
    assert out2.returncode == 0, f"post-reload bots failed:\n{out2.stdout}\n{out2.stderr}"
    assert "100/100 bots OK" in out2.stdout
    assert vis_checks(out2.stdout) > 0, \
        "visibility oracle never asserted anything:\n" + out2.stdout
    r = cli(["stop", "-d", run])
    assert r.returncode == 0


def test_cli_start_fails_at_once_when_a_game_dies_before_ready(rundir,
                                                               tmp_path):
    """A game that exits during boot (a broken script here; a second tpu
    game on a one-chip host in production) fails ``start`` when it exits,
    not after the readiness timeout, with the end of its log shown."""
    _tmp, cfg, _gate = rundir
    script = tmp_path / "broken_game.py"
    script.write_text("raise RuntimeError('game boot failed on purpose')\n")
    t0 = time.monotonic()
    r = cli(["start", "-c", cfg, "-s", str(script), "-d",
             str(tmp_path / "run")])
    assert r.returncode == 1, r.stdout + r.stderr
    assert time.monotonic() - t0 < 25
    assert "game boot failed on purpose" in r.stderr
    assert "failed to become ready" in r.stderr
