"""How cpu_trace.xplane.pb was recorded (JAX_PLATFORMS=cpu):
python benchmark/tests/data/make_cpu_trace.py <out.xplane.pb>"""
import glob
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

out = sys.argv[1]
f = jax.jit(lambda x: jnp.sin(x) @ x.T)
x = jnp.ones((256, 256))
f(x).block_until_ready()
with tempfile.TemporaryDirectory() as log_dir:
    with jax.profiler.trace(log_dir):
        with jax.profiler.TraceAnnotation("replay.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("replay.dispatch"):
                    y = f(x)
                with jax.profiler.TraceAnnotation("replay.drain"):
                    y.block_until_ready()
    shutil.copy(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0], out)
