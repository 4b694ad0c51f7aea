"""Multi-device dry-run coverage in-process is impossible (device count is
locked at first jax init), so these tests spawn subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 and lower reduced
configs on a 4x2 mesh — the same code path launch/dryrun.py uses at
(16,16)/(2,16,16).  Marked slow-ish but bounded (~1 min total)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    from repro.configs import get_arch, get_shape
    from repro.launch.mesh import make_auto_mesh
    from repro.launch.steps import (arch_for_shape, input_specs,
                                    make_decode_step, make_prefill_step,
                                    make_train_step)
    from repro.models.stack import Runtime
    from repro.optim import adamw
    from repro.sharding import (batch_shardings, cache_shardings,
                                lora_shardings, opt_state_shardings,
                                params_shardings)
    from repro.analysis.roofline import build_report

    arch, shape_name = sys.argv[1], sys.argv[2]
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    shape = get_shape(shape_name)
    cfg = arch_for_shape(get_arch(arch), shape).reduced(
        num_layers=None or max(2, len(get_arch(arch).pattern)), d_model=256)
    # shrink the global shape so CPU lowering stays fast
    import dataclasses
    shape = dataclasses.replace(shape, seq_len=min(shape.seq_len, 512),
                                global_batch=8)
    rt = Runtime(attn_impl="chunked", kv_chunk=128,
                 remat=(shape.kind == "train"),
                 dp_axes=("data",), tp_axis="model")
    opt = adamw(1e-4)
    args, _ = input_specs(cfg, shape, optimizer=opt)
    if shape.kind == "train":
        step = make_train_step(cfg, rt, opt)
        sh = (params_shardings(args[0], mesh), lora_shardings(args[1], mesh),
              opt_state_shardings(args[2], None, mesh),
              batch_shardings(args[3], mesh))
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, rt)
        sh = (params_shardings(args[0], mesh), lora_shardings(args[1], mesh),
              batch_shardings(args[2], mesh))
    else:
        step = make_decode_step(cfg, rt)
        sh = (params_shardings(args[0], mesh), lora_shardings(args[1], mesh),
              batch_shardings(args[2], mesh), cache_shardings(args[3], mesh),
              batch_shardings(args[4], mesh))
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(step, in_shardings=sh).lower(*args).compile()
    rep = build_report(arch=arch, shape_cfg=shape, mesh_name="4x2", chips=8,
                       compiled=compiled, lowered_text=None, cfg=cfg)
    print(json.dumps({"flops": rep.flops, "coll_bytes": rep.coll_bytes,
                      "dominant": rep.dominant}))
""")


def _run(arch, shape):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch, shape],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["flops"] > 0
    return rep


@pytest.mark.parametrize("arch,shape", [
    ("deepseek-7b", "train_4k"),
    ("olmoe-1b-7b", "train_4k"),
    ("mamba2-2.7b", "decode_32k"),
    ("jamba-1.5-large-398b", "prefill_32k"),
])
def test_small_mesh_dryrun(arch, shape):
    rep = _run(arch, shape)
    assert rep["dominant"] in ("compute", "memory", "collective")
