"""Records ``data/v5e_scopes.xplane.pb`` on a TPU: three calls of a
jitted step whose parts run under the program's layer scopes, each call
under a ``bench.train_step`` span, with a 2 ms host sleep under
``bench.host_wait`` between them.

    python tests/chipbench/record_scopes_fixture.py <out.xplane.pb>

The step: the Pallas ``gam_quant`` kernel under ``mlp/fc1/mor_quant/
fwd_x``, a matmul under ``mlp/fc1/gemm/fwd``, a softmax under
``attn/core``, an update under ``optim`` and a sum under no scope.
Optimization barriers keep XLA from fusing across the scopes.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "src"))

from repro.kernels.gam_quant import gam_quant_blocks  # noqa: E402


@jax.jit
def step(x, w, m):
    with jax.named_scope("mlp"), jax.named_scope("fc1"):
        with jax.named_scope("mor_quant"), jax.named_scope("fwd_x"):
            xq = gam_quant_blocks(x, m, block=(128, 128))[0]
        xq = jax.lax.optimization_barrier(xq)
        with jax.named_scope("gemm"), jax.named_scope("fwd"):
            h = jnp.dot(xq, w, preferred_element_type=jnp.float32)
    h = jax.lax.optimization_barrier(h)
    with jax.named_scope("attn"), jax.named_scope("core"):
        p = jax.nn.softmax(h, axis=-1)
    p = jax.lax.optimization_barrier(p)
    with jax.named_scope("optim"):
        w_new = (w.astype(jnp.float32) * 0.999 - 1e-3 * p).astype(w.dtype)
    w_new = jax.lax.optimization_barrier(w_new)
    return w_new, jnp.sum(w_new.astype(jnp.float32))


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", jax.devices()
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (512, 512), jnp.bfloat16)
    w = jax.random.normal(kw, (512, 512), jnp.bfloat16) * 0.05
    m = jnp.float32(1.0)
    jax.block_until_ready(step(x, w, m))
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.train_step"):
            w, s = step(x, w, m)
            jax.block_until_ready((w, s))
        with jax.profiler.TraceAnnotation("bench.host_wait"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    print(out, os.path.getsize(out))


if __name__ == "__main__":
    main(sys.argv[1])
