"""Beyond-paper demo: RapidGNN's deterministic-schedule + hot-set cache
applied to a vocab-sharded transformer embedding table (DESIGN.md §4).

Shows the offline enumeration (Alg. 1 lines 1-3 on token ids), the
hot-set selection, and the resulting traffic reduction for a Zipf token
stream -- then validates the DEVICE path (a2a pull + cache_gather merge)
against a direct numpy gather.

  PYTHONPATH=src python examples/hot_embedding_cache.py
"""
import numpy as np

from repro.configs import get_arch
from repro.dist.mesh import host_device_flags
from repro.data.pipeline import zipf_tokens, enumerate_token_accesses
from repro.graph.sampler import rng_from
from repro.models.transformer.embedding import HotEmbeddingSim

arch = "gemma2-2b"
cfg = get_arch(arch)
workers, batch, seq, steps = 8, 16, 256, 100

print(f"arch {arch}: vocab {cfg.vocab_size}, d_model {cfg.d_model}")
print("1) offline enumeration of the run's token accesses ...")
counts = enumerate_token_accesses(cfg, batch, seq, steps, s0=7)
nz = counts[counts > 0]
print(f"   {nz.size} unique tokens accessed; "
      f"{(nz == 1).mean():.1%} exactly once; max freq {nz.max()} "
      f"(the paper's Fig. 3 long tail, on text)")

print("2) hot-set caches per worker + traffic accounting ...")
for n_hot in (4096, 32768):
    sim = HotEmbeddingSim(vocab=cfg.vocab_size, d=cfg.d_model,
                          num_workers=workers, n_hot=n_hot, counts=counts)
    base = cach = 0
    for i in range(steps):
        toks = zipf_tokens(rng_from(7, 0, i), cfg.vocab_size, (batch, seq))
        b, c, _ = sim.batch_traffic(toks, worker=0)
        base += b
        cach += c
    cach += sim.cache_build_bytes()
    print(f"   n_hot {n_hot:6d}: baseline {base/1e6:7.1f} MB -> "
          f"cached {cach/1e6:7.1f} MB  ({base/max(cach,1):.2f}x less)")

print("3) device-path validation (4 emulated devices) ...")
import os
import subprocess
import sys
code = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.dist import make_mesh, build_pull_plan
from repro.models.transformer.embedding import device_embedding_lookup
P_, vper, d, m = 4, 64, 16, 24
rng = np.random.default_rng(0)
table = rng.normal(size=(P_*vper, d)).astype(np.float32)
owner = np.repeat(np.arange(P_), vper)
mesh = make_mesh((P_,), ("data",))
# per-worker token batch + (empty-cache) pull plan
toks, plans, want = [], [], []
for w in range(P_):
    t = rng.integers(0, P_*vper, size=m)
    toks.append(t)
    plans.append(build_pull_plan(t.astype(np.int32), np.arange(m, dtype=np.int32),
                                 owner, P_, m))
    want.append(table[t])
plan = {
  "send_ids": jnp.asarray(np.stack([p.send_ids for p in plans])),
  "send_pos": jnp.asarray(np.stack([p.send_pos for p in plans])),
  "send_mask": jnp.asarray(np.stack([p.send_mask for p in plans])),
  "offsets": jnp.asarray((np.arange(P_)*vper).astype(np.int32)),
}
cache_ids = jnp.full((P_, 4), 2**31 - 1, jnp.int32)
cache_feats = jnp.zeros((P_, 4, d), jnp.float32)
with mesh:
    out = device_embedding_lookup(mesh, jnp.asarray(table.reshape(P_, vper, d)),
                                  cache_ids, cache_feats,
                                  jnp.asarray(np.stack(toks), jnp.int32), plan, m)
np.testing.assert_allclose(np.asarray(out), np.stack(want), rtol=1e-6)
print("   device embedding lookup == direct gather OK")
"""
env = dict(os.environ)
env["XLA_FLAGS"] = host_device_flags(4, env.get("XLA_FLAGS", ""))
env.setdefault("PYTHONPATH", "src")
r = subprocess.run([sys.executable, "-c", code], env=env,
                   capture_output=True, text=True)
print(r.stdout.strip() or r.stderr[-500:])
assert r.returncode == 0
print("OK")
