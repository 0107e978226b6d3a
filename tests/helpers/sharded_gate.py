"""Small-mesh sharding gate (run as a subprocess: needs 8 fake devices).

For each arch family, runs the *sharded* train step / prefill / decode on
a (2,4) data x model mesh and checks numerical parity against the
unsharded single-logical-device path — catching sharding-rule regressions
long before the 512-device dry-run.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()

import sys  # noqa: E402
from dataclasses import replace  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.configs import get_arch, smoke  # noqa: E402
from repro.launch.mesh import make_debug_mesh, make_mesh_info  # noqa: E402
from repro.launch.train import make_train_step  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.parallel import sharding as sh  # noqa: E402


def check(arch_id: str, tweak=None, tol=5e-3):
    cfg = smoke(get_arch(arch_id))
    if tweak:
        cfg = replace(cfg, **tweak)
    mesh = make_debug_mesh(2, 4)
    mi = make_mesh_info(mesh)
    mode = sh.attn_mode(cfg, mi)

    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(params)
    B, S, nm = 4, 16, 2
    rng = np.random.RandomState(0)
    if cfg.input_mode == "embeds":
        batch = {"embeds": jnp.asarray(rng.standard_normal(
                     (nm, B // nm, S, cfg.d_model)), jnp.float32),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab,
                                                   (nm, B // nm, S)))}
    else:
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab,
                                                   (nm, B // nm, S))),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab,
                                                   (nm, B // nm, S)))}

    # unsharded reference
    ref_step = jax.jit(make_train_step(cfg, None))
    p_ref, o_ref, m_ref = ref_step(params, opt, batch)

    # sharded
    with jax.set_mesh(mesh):
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           sh.param_specs(cfg, mi),
                           is_leaf=lambda x: isinstance(x, P))
        params_s = jax.device_put(params, psh)
        opt_s = adamw.init(params_s)
        step = jax.jit(make_train_step(cfg, mi))
        p_s, o_s, m_s = step(params_s, opt_s, batch)

    dl = abs(float(m_ref["loss"]) - float(m_s["loss"]))
    # parameter drift after one update
    dmax = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))
               for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_s)))
    ok = dl < tol and dmax < tol
    print(f"{arch_id:16s} mode={mode:9s} dloss={dl:.2e} dparam={dmax:.2e} "
          f"{'OK' if ok else 'FAIL'}", flush=True)
    return ok


def main():
    results = [
        check("qwen3_4b"),                                  # megatron GQA
        check("qwen3_4b", {"n_heads": 6, "n_kv_heads": 3,
                           "d_model": 192}),                # context mode
        # EP with capacity high enough that nothing drops: isolates the
        # sharding math from (intended) GShard capacity-dropping effects
        check("olmoe_1b_7b", {"moe_capacity_factor": 8.0}),  # MoE EP
        check("mixtral_8x7b", {"n_experts": 2}),            # MoE TP branch
        check("mamba2_1_3b"),                               # SSM
        check("zamba2_7b"),                                 # hybrid + shared
        check("gemma3_4b"),                                 # local/global+tied
    ]
    if not all(results):
        sys.exit(1)
    print("ALL SHARDED PARITY CHECKS PASSED")


if __name__ == "__main__":
    main()
