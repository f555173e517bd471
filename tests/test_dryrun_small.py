"""Dry-run machinery on a small fake-device mesh (subprocess: the device
count must be set before jax initializes)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.configs.shapes import InputShape
    from repro.fed.trilevel_llm import FedHyper
    from repro.launch import dryrun as dr
    from repro.launch import roofline as rl

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = reduced(get_config("{arch}"))
    shape = InputShape("{kind}_small", seq_len=64, global_batch=4,
                       kind="{kind}")
    hyper = FedHyper(n_workers=2, cut_mode="sketch", sketch_r=64,
                     p_max=2, k_inner=1, remat=False, unroll=False)
    if "{kind}" == "train":
        fn, args, shardings = dr.build_train(cfg, shape, mesh, hyper,
                                             "train")
    elif "{kind}" == "prefill":
        fn, args, shardings = dr.build_prefill(cfg, shape, mesh,
                                               unroll=False)
    else:
        fn, args, shardings = dr.build_decode(cfg, shape, mesh,
                                              unroll=False)
    named = jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        shardings, is_leaf=lambda x: isinstance(x, P))
    with mesh:
        compiled = jax.jit(fn, in_shardings=named).lower(*args).compile()
    ca = compiled.cost_analysis()
    coll = rl.collective_bytes(compiled.as_text())
    print(json.dumps({{"flops": ca.get("flops", 0.0),
                       "coll_count": coll["count"]}}))
""")


@pytest.mark.parametrize("arch,kind", [
    ("llama3-8b", "train"),
    ("mixtral-8x22b", "prefill"),
    ("jamba-v0.1-52b", "decode"),
    ("whisper-large-v3", "decode"),
])
def test_small_mesh_dryrun(arch, kind):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    env["JAX_PLATFORMS"] = "cpu"   # fake CPU devices, never the chip
    script = _SCRIPT.format(arch=arch, kind=kind)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 0
