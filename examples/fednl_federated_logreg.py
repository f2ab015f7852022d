"""End-to-end paper experiment: FedNL vs first-order baselines on the
a1a-shaped problem (Table 3 sizes), with the paper's communicated-bits
accounting, PLUS the same FedNL executed distributed via shard_map (the
production execution path, silo data sharded over the mesh).

    PYTHONPATH=src python examples/fednl_federated_logreg.py
"""

import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp
import numpy as np

from repro.core import RandomDithering
from repro.core.baselines import Diana, gd_run
from repro.core.compressors import FLOAT_BITS
from repro.core.newton import newton_run
from repro.core.objectives import (batch_grad, batch_hess, global_value,
                                   lipschitz_constants)
from repro.data.synthetic import make_libsvm_like
from repro.engine import ExperimentSpec, Sweep

data = make_libsvm_like(jax.random.PRNGKey(0), "a1a", lam=1e-3)
n, m, d = data.a.shape
grad_fn = lambda x: batch_grad(x, data)
hess_fn = lambda x: batch_hess(x, data)
val_fn = lambda x: global_value(x, data)
consts = lipschitz_constants(data)
xstar, _ = newton_run(jnp.zeros(d), grad_fn, hess_fn, 25)
fstar = float(val_fn(xstar))
x0 = xstar + 0.05 * jax.random.normal(jax.random.PRNGKey(1), (d,))
prob = dict(data=data, grad=grad_fn, hess=hess_fn, val=val_fn, n=n, d=d,
            fstar=fstar)

print(f"a1a-shaped: n={n} silos, m={m} points/silo, d={d}, "
      f"kappa~{consts['L'] / 1e-3:.0f}")

# --- FedNL (vmap execution through the engine) --------------------------------
spec = ExperimentSpec("fednl", "rankr", 1, params=dict(option=1, mu=1e-3),
                      num_rounds=20, name="FedNL-Rank1")
cell = Sweep([spec]).run(prob, x0=x0).cells[0]
print("\nFedNL (Rank-1):    bits/node        f - f*")
for k in (0, 2, 5, 10, 15, 20):
    print(f"  round {k:3d}   {cell.bits[k]:12.3e}   {cell.gaps[0, k]:.3e}")

# --- the same spec, sharded over the mesh (core/federated.py path) ------------
from repro.launch.mesh import make_mesh
mesh = make_mesh((jax.device_count(),), ("data",))
spec_sh = ExperimentSpec("fednl", "rankr", 1, params=dict(option=2),
                         num_rounds=10, name="FedNL-sharded")
cell_sh = Sweep([spec_sh], mesh=mesh).run(prob, x0=x0).cells[0]
print(f"\nshard_map execution over {jax.device_count()} device(s): "
      f"gap after 10 rounds = {cell_sh.gaps[0, -1]:.3e}")

# --- baselines ------------------------------------------------------------------
_, xs_gd = gd_run(x0, grad_fn, 1.0 / consts["L"], 2000)
rd = RandomDithering(s=int(d ** 0.5))
diana = Diana(grad_fn, rd, consts["L"], n, rd.spec((d,)).omega)
_, xs_di = diana.run(x0, n, 2000)

gap_gd = float(val_fn(xs_gd[-1])) - fstar
gap_di = float(val_fn(xs_di[-1])) - fstar
bits_gd = 2000 * d * FLOAT_BITS
bits_di = 2000 * diana.bits_per_round(d)
print(f"\nGD    after {bits_gd:.2e} bits/node: gap {gap_gd:.3e}")
print(f"DIANA after {bits_di:.2e} bits/node: gap {gap_di:.3e}")
print(f"FedNL after {cell.bits[20]:.2e} bits/node: gap "
      f"{cell.gaps[0, 20]:.3e}   <-- the paper's headline")
