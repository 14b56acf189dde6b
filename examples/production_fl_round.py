"""Production-regime FL round on a multi-device mesh (runs on CPU host
devices; the same code drives the 512-chip dry-run).

Spawns itself with XLA_FLAGS so the demo works from a plain shell.  CPU
only: a parent that has touched JAX holds the accelerator, so it must
never spawn children that need the chip.

    PYTHONPATH=src python examples/production_fl_round.py --arch qwen2.5-14b
"""
import argparse
import os
import subprocess
import sys

INNER = """
import jax, jax.numpy as jnp, time
from repro.configs import get_arch
from repro.launch.train import make_fl_round_step, FLStepConfig
from repro.models import transformer as T
from repro.data.synthetic import synth_token_batch

arch_id = %(arch)r
mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = get_arch(arch_id, smoke=True)
fl = FLStepConfig(aggregator=%(agg)r, local_steps=2, lr=0.01, c=0.1)
step, _ = make_fl_round_step(cfg, mesh, "data", fl, jnp.float32)

key = jax.random.PRNGKey(0)
params = T.init_params(key, cfg)
reference = jax.tree.map(jnp.zeros_like, params)
U, B, S = 2, 8, 64
tb = synth_token_batch(key, U * B, S, cfg.vocab)
batch = {k: v.reshape(U, B, S) for k, v in tb.items()}
root = {k: v[:, :2] for k, v in batch.items()}

with mesh:
    for r in range(4):
        t0 = time.time()
        args = (params, reference, batch) + ((root,) if %(agg)r == "br_drag" else ())
        params, reference, m = step(*args)
        jax.block_until_ready(m["delta_norm"])
        print(f"round {r}: DoD={float(m['dod_mean']):.4f} "
              f"|delta|={float(m['delta_norm']):.4f} ({time.time()-t0:.2f}s)")
print("4 clients x", U, "local steps per round; one pmean per round - done")
"""


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-14b")
    ap.add_argument("--aggregator", default="drag", choices=["drag", "br_drag", "fedavg"])
    args = ap.parse_args()
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONPATH", "src")
    code = INNER % {"arch": args.arch, "agg": args.aggregator}
    raise SystemExit(subprocess.call([sys.executable, "-c", code], env=env))


if __name__ == "__main__":
    main()
