"""Record the small trace `tests/recorded/small.xplane.pb` on the chip (run once,
by hand: `chiprun -- python benchmark/tests/record_trace.py <out_dir>`).

Two jitted programs with the names the readers look for (`jit_fn`,
`jit_prefill`), a handful of calls each inside the harness's own window
annotation, nothing else on the device. Writes the trace and what
`trace_reduce.reduce` makes of it (the test's expected numbers).
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    import trace_reduce

    def fn(x):
        return (x @ x) * (1.0 / x.shape[0])

    def prefill(x):
        return jnp.tanh(x @ x.T).sum()

    f, p = jax.jit(fn), jax.jit(prefill)
    x = jnp.ones((512, 512), jnp.bfloat16)
    f(x).block_until_ready()
    p(x).block_until_ready()
    out = Path(out_dir)
    tmp = out / "raw"
    jax.profiler.start_trace(str(tmp))
    with jax.profiler.TraceAnnotation("benchmark.window"):
        for _ in range(5):
            f(x).block_until_ready()
            time.sleep(0.002)
        for _ in range(3):
            p(x).block_until_ready()
    jax.profiler.stop_trace()
    src = trace_reduce.find_xplane(tmp)
    shutil.copyfile(src, out / "small.xplane.pb")
    red = trace_reduce.reduce(out / "small.xplane.pb")
    (out / "small.expected.json").write_text(json.dumps(red, indent=1))
    (out / "small.inventory.json").write_text(
        json.dumps(trace_reduce.inventory(out / "small.xplane.pb"), indent=1))
    shutil.rmtree(tmp)
    print(json.dumps({k: red[k] for k in ("window_s", "busy_s", "modules")}))


if __name__ == "__main__":
    main(sys.argv[1])
