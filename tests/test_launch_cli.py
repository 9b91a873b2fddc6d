"""launch CLI end-to-end (reference analog: test/legacy_test/
test_launch_coverage.py; python -m paddle.distributed.launch;
multi-node rendezvous launch/controllers/collective.py:37; restart
--max_restart policy)."""
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_launch_two_procs_dp(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(
        """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as pt
import paddle_tpu.distributed as dist

dist.init_parallel_env(backend="cpu")
r = dist.get_rank()
assert dist.get_world_size() == 2
pt.seed(1)
model = pt.DataParallel(pt.nn.Linear(4, 2))
opt = pt.optimizer.SGD(parameters=model.parameters(), learning_rate=0.1)
np.random.seed(r)
loss = (model(pt.to_tensor(np.random.randn(8, 4).astype(np.float32))) ** 2).mean()
loss.backward()
opt.step()
print(f"RANK{r}_DONE", flush=True)
dist.barrier()  # rank0 hosts the store: leave together
""")
    log_dir = str(tmp_path / "logs")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the launcher must inject its own package root into the workers;
    # drop any inherited PYTHONPATH so this test actually guards that
    env.pop("PYTHONPATH", None)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", log_dir, str(script)],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=repo_root)
    assert out.returncode == 0, out.stdout + out.stderr
    # per-rank logs exist and both ranks completed
    logs = os.listdir(log_dir)
    assert logs, "no per-rank log files written"
    combined = out.stdout + out.stderr
    for f in logs:
        combined += open(os.path.join(log_dir, f)).read()
    assert "RANK0_DONE" in combined
    assert "RANK1_DONE" in combined


def test_launch_two_nodes_rendezvous(tmp_path):
    """Two launcher processes with distinct node ranks rendezvous through
    the TCPStore master and train together (VERDICT r1 next #5)."""
    script = tmp_path / "train.py"
    script.write_text(
        """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as pt
import paddle_tpu.distributed as dist

eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
assert len(eps) == 4, eps
assert all(":" in e for e in eps)
# endpoints are real (rendezvoused), not the master port
dist.init_parallel_env(backend="cpu")
r = dist.get_rank()
assert dist.get_world_size() == 4
x = pt.to_tensor(np.full((2,), float(r + 1), np.float32))
dist.all_reduce(x)
assert float(x.numpy()[0]) == 10.0, x.numpy()  # 1+2+3+4
print(f"NODE{os.environ['PADDLE_NODE_RANK']}_RANK{r}_OK", flush=True)
dist.barrier()
""")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    master = f"127.0.0.1:{_free_port()}"
    launchers = []
    for node in range(2):
        launchers.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "2",
             "--master", master, "--rank", str(node),
             "--log_dir", str(tmp_path / f"logs{node}"), str(script)],
            env=env, cwd=repo_root,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = []
    for p in launchers:
        out, _ = p.communicate(timeout=240)
        outs.append(out)
    assert all(p.returncode == 0 for p in launchers), outs
    combined = "".join(outs)
    for node in range(2):
        for f in os.listdir(tmp_path / f"logs{node}"):
            combined += open(tmp_path / f"logs{node}" / f).read()
    for r in range(4):
        assert f"_RANK{r}_OK" in combined, combined


def test_launch_restart_on_failure(tmp_path):
    """A worker that dies is relaunched (--max_restart): first generation
    crashes, restart succeeds (reference: elastic manager.py:457-530)."""
    marker = tmp_path / "crashed_once"
    script = tmp_path / "train.py"
    script.write_text(f"""
import os, sys
marker = {str(marker)!r}
if os.environ["PADDLE_TRAINER_ID"] == "1" and not os.path.exists(marker):
    open(marker, "w").write("x")
    sys.exit(17)   # simulated fault on first generation
print("RANK" + os.environ["PADDLE_TRAINER_ID"] + "_GEN_OK", flush=True)
""")
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restart", "2",
         "--log_dir", str(tmp_path / "logs"), str(script)],
        capture_output=True, text=True, timeout=240, env=env, cwd=repo_root)
    assert out.returncode == 0, out.stdout + out.stderr
    assert marker.exists()
    combined = out.stdout + out.stderr
    for f in os.listdir(tmp_path / "logs"):
        combined += open(tmp_path / "logs" / f).read()
    assert "RANK0_GEN_OK" in combined
    assert "RANK1_GEN_OK" in combined


def test_launch_restart_exhausted(tmp_path):
    """Permanent fault: exit code propagates once --max_restart is used."""
    script = tmp_path / "train.py"
    script.write_text("import sys; sys.exit(9)\n")
    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restart", "1",
         "--log_dir", str(tmp_path / "logs"), str(script)],
        capture_output=True, text=True, timeout=240, env=env, cwd=repo_root)
    assert out.returncode != 0
