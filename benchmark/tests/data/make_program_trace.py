"""How program_trace.xplane.pb and program_trace.hlo.txt were recorded,
from the repository's root (JAX_PLATFORMS=cpu), compiled under the
relative name so that the recorded source locations name no directory:

    python -c "import sys; p = sys.argv[0] = \
        'benchmark/tests/data/make_program_trace.py'; \
        sys.argv[1:] = ['<out_dir>']; \
        exec(compile(open(p).read(), p, 'exec'), {'__name__': '__main__'})"

A jitted `program` with stage scopes (a `ct` gather whose fusion's root
is a `lattice` add, an `accounting` sort, an unscoped sort) runs three
times inside `replay.window`, each launch under the host spans a
persistent launch records: `datapath.stack`, `datapath.upload`,
`datapath.enqueue` and `datapath.outputs`."""
import glob
import os
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

out = sys.argv[1]
N = 1 << 16


def program(x, idx):
    with jax.named_scope("ct"):
        g = x[idx]
    with jax.named_scope("lattice"):
        y = (g * 3.0 + 1.0).reshape(4, -1)
    with jax.named_scope("accounting"):
        s = jnp.sort(y, axis=1)
    return y, s, jnp.sort(x)


f = jax.jit(program)
rng = np.random.default_rng(0)
parts = [rng.random(N // 4, np.float32) for _ in range(4)]
idx = jnp.asarray(rng.integers(0, N, N, dtype=np.int32))
jax.block_until_ready(f(jnp.ones(N), idx))
with tempfile.TemporaryDirectory() as log_dir:
    with jax.profiler.trace(log_dir):
        with jax.profiler.TraceAnnotation("replay.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("datapath.stack"):
                    host = np.concatenate(parts)
                with jax.profiler.TraceAnnotation("datapath.upload"):
                    x = jax.device_put(host)
                with jax.profiler.TraceAnnotation("datapath.enqueue"):
                    y, s, u = f(x, idx)
                with jax.profiler.TraceAnnotation("datapath.outputs"):
                    outs = [y[i] for i in range(4)]
                with jax.profiler.TraceAnnotation("replay.drain"):
                    jax.block_until_ready((outs, s, u))
    shutil.copy(
        glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0],
        os.path.join(out, "program_trace.xplane.pb"),
    )
with open(os.path.join(out, "program_trace.hlo.txt"), "w") as fh:
    fh.write(f.lower(jnp.ones(N), idx).compile().as_text())
