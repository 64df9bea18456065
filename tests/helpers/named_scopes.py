"""The named scopes in compiled train steps' ``op_name`` metadata.

Imported by ``tests/test_named_scopes.py`` for the one-device plan; run as
a script for the plans over several devices, on four virtual CPU devices,
printing one line ``SCOPES <json>``: per plan, each scope of
``bench/scopes.py`` found and the passes it was found in.
"""
import json
import os
import re
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def scopes_of(text: str) -> dict:
    """``{scope: sorted passes}`` over every ``op_name`` of an HLO text."""
    from bench import scopes as bs

    out: dict[str, set] = {}
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        pas = bs.attribute(op_name)[2]
        for part in op_name.split("/"):
            if part in bs.SCOPES:
                out.setdefault(part, set()).add(pas)
    return {k: sorted(v) for k, v in out.items()}


def step_text(dp: int, pp: int, zero_stage: int) -> str:
    """The compiled ``pipeline_step`` of the small UViT on a ``(dp, pp)``
    mesh, as HLO text."""
    import jax
    import jax.numpy as jnp

    from repro.launch.train import pipeline_plan, pipeline_step
    from repro.optim import AdamWConfig, adamw_init

    args = types.SimpleNamespace(
        arch="uvit", layers=None, dp=dp, pp=pp, global_batch=8,
        microbatches=4, zero_stage=zero_stage, interleave=None,
        wire_dtype="bfloat16")
    cfg, compiled = pipeline_plan(args)
    mesh = jax.make_mesh((dp, pp), ("data", "model"),
                         devices=jax.devices()[:dp * pp])
    step, _ = pipeline_step(compiled, cfg, mesh, AdamWConfig(lr=1e-4))
    params = jax.eval_shape(compiled.init_pipeline_params,
                            jax.random.PRNGKey(0))
    batch = {"latents": jax.ShapeDtypeStruct(
                 (8, cfg.img_size, cfg.img_size, cfg.in_ch), jnp.float32),
             "labels": jax.ShapeDtypeStruct((8,), jnp.int32)}
    return step.lower(params, jax.eval_shape(adamw_init, params), batch,
                      jax.ShapeDtypeStruct((2,), jnp.uint32),
                      jax.ShapeDtypeStruct((), jnp.float32)
                      ).compile().as_text()


def linear_text() -> str:
    """Loss and gradients of a skip-free LM through the linear table
    executor over two devices, compiled, as HLO text."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import AttnConfig
    from repro.models.lm import LMConfig, lm_pipeline_graph
    from repro.runtime.adapters import lm_model_fns
    from repro.runtime.compile import auto_pipeline

    cfg = LMConfig(name="t", vocab=64, d_model=32, n_layers=4,
                   attn=AttnConfig(32, 4, 2, 8), d_ff=64,
                   tied_embeddings=True)
    cp = auto_pipeline(lm_pipeline_graph(cfg), lm_model_fns(cfg), 2,
                       pipeline_devices=2, microbatches=4)
    assert not cp.folded
    mesh = jax.make_mesh((1, 2), ("data", "model"),
                         devices=jax.devices()[:2])
    state = jax.eval_shape(
        lambda k: cp.split_params(cp.model_fns.init_fn(k)),
        jax.random.PRNGKey(0))
    mbs = {"tokens": jax.ShapeDtypeStruct((4, 2, 16), jnp.int32)}
    return jax.jit(jax.value_and_grad(cp.bind(mesh))).lower(
        state, mbs).compile().as_text()


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    found = {"wave_p2": scopes_of(step_text(1, 2, 0)),
             "wave_p2_dp2_zero2": scopes_of(step_text(2, 2, 2)),
             "linear_p2": scopes_of(linear_text())}
    print("SCOPES " + json.dumps(found))
