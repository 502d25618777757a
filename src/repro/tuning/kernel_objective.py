"""Kernel-autotuning objective — tune the repo's *own* Pallas kernels.

The paper tunes a real framework backend; this module closes the same
loop for the repo's kernels: the search space is the Pallas tile/grid
knobs each kernel actually takes (``block_q``, ``block_kv``,
``block_rows``, ``chunk``, ``block_d``), the measurement is the shared
variance-adaptive :class:`~repro.tuning.evaluator.WallClockEvaluator`
loop, and the product is a best-known config per (kernel, shape bucket,
hardware) persisted in :class:`~repro.tuning.tundb.TuningDB` by
``benchmarks/kernel_sweep.py``.

Two measurement modes:

* **in-process** (default) — the kernel runs through the public
  ``repro.kernels.ops`` dispatch with ``impl="pallas"`` (interpret mode
  on CPU, the real kernel on TPU).  Cheap enough for CI smoke; relative
  tile rankings on CPU-interpret are a proxy, real timing is the
  ``slow``-gated TPU path.
* **subprocess** — for the *host-level* knobs of the SNIPPETS.md
  exemplars (``--xla_force_host_platform_device_count``, extra
  ``XLA_FLAGS``) that cannot change inside a live process: jax reads
  ``XLA_FLAGS`` once at first import, so points carrying host knobs are
  measured by re-invoking ``python -m repro.tuning.kernel_objective``
  with the flags in the child environment (the paper's
  fresh-process-per-measurement harness).  Orders of magnitude more
  expensive per point; gated ``slow`` in tests.

Point hygiene mirrors the ``config_from_point`` fix: a point key that
is neither a knob of the targeted kernel nor a recognized host knob
raises ``ValueError`` — a typo'd dim must never silently tune nothing.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
from typing import Dict, Optional, Tuple

from repro.tuning.objective import Evaluator, refuse_child_on_tpu

#: host-level knobs (subprocess-only; see module docstring)
HOST_KNOBS = ("host_devices", "xla_flags")

#: XLA_FLAGS presets worth trying on a CPU host (exemplar-derived)
XLA_FLAG_PRESETS = (
    "",
    "--xla_cpu_multi_thread_eigen=true",
    "--xla_cpu_multi_thread_eigen=false",
)


def _pow2_choices(lo: int, hi: int) -> "list[int]":
    v, out = lo, []
    while v <= hi:
        out.append(v)
        v *= 2
    return out or [lo]


# ---------------------------------------------------------------------------
# Kernel registry: shapes, tunable knobs, search space, step builders
# ---------------------------------------------------------------------------


class KernelSpec:
    """One tunable kernel: its call-shape dims, knob names, search
    space, and a ``WallClockEvaluator``-style step builder."""

    def __init__(self, name: str, shape: Dict[str, int], knobs: tuple,
                 space_fn, build_fn, examples_fn):
        self.name = name
        self.shape = dict(shape)
        self.knobs = tuple(knobs)
        self._space_fn = space_fn
        self._build_fn = build_fn
        self._examples_fn = examples_fn

    def space(self, shape: Optional[Dict[str, int]] = None) -> "list[dict]":
        return self._space_fn(dict(self.shape if shape is None else shape))

    def build(self, shape: Dict[str, int], point: Dict):
        """-> (step_fn, args, examples_per_step) for WallClockEvaluator."""
        stray = sorted(k for k in point if k not in self.knobs)
        if stray:
            raise ValueError(
                f"point keys {stray} are not knobs of kernel "
                f"{self.name!r} (knobs: {sorted(self.knobs)})")
        step, args = self._build_fn(shape, point)
        return step, args, float(self._examples_fn(shape))


def _attn_space(s):
    return [
        {"name": "block_q", "type": "cat",
         "choices": _pow2_choices(8, max(8, s["Sq"]))},
        {"name": "block_kv", "type": "cat",
         "choices": _pow2_choices(8, max(8, s["Sk"]))},
    ]


def _build_flash(s, point):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (s["B"], s["Sq"], s["H"], s["dh"]), jnp.float32)
    k = jax.random.normal(kk, (s["B"], s["Sk"], s["K"], s["dh"]), jnp.float32)
    v = jax.random.normal(kv, (s["B"], s["Sk"], s["K"], s["dh"]), jnp.float32)
    bq = int(point.get("block_q", 128))
    bkv = int(point.get("block_kv", 128))

    def step(q, k, v):
        return ops.attention(q, k, v, causal=True, impl="pallas",
                             block_q=bq, block_kv=bkv)

    return step, (q, k, v)


def _decode_space(s):
    return [{"name": "block_kv", "type": "cat",
             "choices": _pow2_choices(8, max(8, s["Smax"]))}]


def _build_decode(s, point):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (s["B"], s["H"], s["dh"]), jnp.float32)
    k = jax.random.normal(kk, (s["B"], s["Smax"], s["K"], s["dh"]), jnp.float32)
    v = jax.random.normal(kv, (s["B"], s["Smax"], s["K"], s["dh"]), jnp.float32)
    lengths = jnp.full((s["B"],), s["Smax"] // 2, jnp.int32)
    bkv = int(point.get("block_kv", 512))

    def step(q, k, v, lengths):
        return ops.decode_attention(q, k, v, lengths, impl="pallas",
                                    block_kv=bkv)

    return step, (q, k, v, lengths)


def _rms_space(s):
    return [{"name": "block_rows", "type": "cat",
             "choices": _pow2_choices(8, max(8, s["rows"]))}]


def _build_rms(s, point):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (s["rows"], s["D"]),
                          jnp.float32)
    scale = jnp.ones((s["D"],), jnp.float32)
    br = int(point.get("block_rows", 256))

    def step(x, scale):
        return ops.rmsnorm(x, scale, impl="pallas", block_rows=br)

    return step, (x, scale)


def _ssm_space(s):
    return [
        {"name": "chunk", "type": "cat",
         "choices": _pow2_choices(8, max(8, s["S"]))},
        {"name": "block_d", "type": "cat",
         "choices": _pow2_choices(8, max(8, s["D"]))},
    ]


def _build_ssm(s, point):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, D, N = s["B"], s["S"], s["D"], s["N"]
    x = jax.random.normal(ks[0], (B, S, D), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, D), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (D, N), jnp.float32))
    B_in = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    C_in = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    D_skip = jnp.ones((D,), jnp.float32)
    chunk = int(point.get("chunk", 128))
    bd = int(point.get("block_d", 256))

    def step(x, dt, A, B_in, C_in, D_skip):
        return ops.ssm_scan(x, dt, A, B_in, C_in, D_skip, impl="pallas",
                            chunk=chunk, block_d=bd)

    return step, (x, dt, A, B_in, C_in, D_skip)


def _gla_space(s):
    return [{"name": "chunk", "type": "cat",
             "choices": _pow2_choices(8, max(8, s["S"]))}]


def _build_gla(s, point):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, H, dk, dv = s["B"], s["S"], s["H"], s["dk"], s["dv"]
    r = jax.random.normal(ks[0], (B, S, H, dk), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, dk), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, dv), jnp.float32)
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, S, H, dk), jnp.float32)))
    u = jax.random.normal(ks[4], (H, dk), jnp.float32)
    chunk = int(point.get("chunk", 64))

    def step(r, k, v, w, u):
        return ops.gla_scan(r, k, v, w, u, impl="pallas", chunk=chunk)

    return step, (r, k, v, w, u)


#: tiny interpret-mode-friendly default shapes; real-timing sweeps pass
#: production shapes explicitly
KERNELS: Dict[str, KernelSpec] = {
    "flash_attention": KernelSpec(
        "flash_attention",
        {"B": 2, "Sq": 64, "Sk": 64, "H": 2, "K": 2, "dh": 16},
        ("block_q", "block_kv"), _attn_space, _build_flash,
        lambda s: s["B"] * s["Sq"]),
    "decode_attention": KernelSpec(
        "decode_attention",
        {"B": 2, "H": 2, "K": 2, "dh": 16, "Smax": 64},
        ("block_kv",), _decode_space, _build_decode,
        lambda s: s["B"]),
    "rmsnorm": KernelSpec(
        "rmsnorm",
        {"rows": 128, "D": 128},
        ("block_rows",), _rms_space, _build_rms,
        lambda s: s["rows"]),
    "ssm_scan": KernelSpec(
        "ssm_scan",
        {"B": 2, "S": 64, "D": 32, "N": 8},
        ("chunk", "block_d"), _ssm_space, _build_ssm,
        lambda s: s["B"] * s["S"]),
    "gla_scan": KernelSpec(
        "gla_scan",
        {"B": 2, "S": 64, "H": 2, "dk": 16, "dv": 16},
        ("chunk",), _gla_space, _build_gla,
        lambda s: s["B"] * s["S"]),
}


def kernel_space(kernel: str, shape: Optional[Dict[str, int]] = None,
                 *, host_knobs: bool = False) -> "list[dict]":
    """SearchSpace dims for one kernel (optionally + host-level knobs).

    ``host_knobs=True`` appends the SNIPPETS.md exemplar knobs
    (``host_devices`` → ``--xla_force_host_platform_device_count``,
    ``xla_flags`` presets); those points require an evaluator with
    ``allow_subprocess=True``.
    """
    dims = KERNELS[kernel].space(shape)
    if host_knobs:
        ncpu = os.cpu_count() or 1
        dims += [
            {"name": "host_devices", "type": "cat",
             "choices": [n for n in (1, 2, 4, 8) if n <= ncpu] or [1]},
            {"name": "xla_flags", "type": "cat",
             "choices": list(XLA_FLAG_PRESETS)},
        ]
    return dims


# ---------------------------------------------------------------------------
# Evaluator
# ---------------------------------------------------------------------------


class KernelTuneEvaluator(Evaluator):
    """Measured throughput (examples/s) of one Pallas kernel at one shape.

    Implements the evaluator protocol incl. fidelity by delegating to
    :class:`~repro.tuning.evaluator.WallClockEvaluator`; a full-fidelity
    call is byte-identical to a plain call (golden-trace contract).

    Points carrying host knobs (``host_devices``, ``xla_flags``) are
    measured in a fresh subprocess with ``XLA_FLAGS`` set in the child
    environment — iff ``allow_subprocess=True``; otherwise they raise,
    because a live process cannot re-read ``XLA_FLAGS``.
    """

    supports_fidelity = True

    def __init__(self, kernel: str, shape: Optional[Dict[str, int]] = None,
                 *, warmup: int = 1, iters: int = 3, adaptive: bool = True,
                 rel_halfwidth: float = 0.2,
                 allow_subprocess: bool = False, timeout: float = 300.0):
        if kernel not in KERNELS:
            raise ValueError(
                f"unknown kernel {kernel!r}; one of {sorted(KERNELS)}")
        self.kernel = kernel
        self.spec = KERNELS[kernel]
        self.shape = dict(self.spec.shape if shape is None else shape)
        self.allow_subprocess = allow_subprocess
        self.timeout = float(timeout)
        self._harness = dict(warmup=warmup, iters=iters, adaptive=adaptive,
                             rel_halfwidth=rel_halfwidth)
        # lazy import keeps this module importable without jax on the
        # harness side (the subprocess child imports it before jax init)
        from repro.tuning.evaluator import WallClockEvaluator

        self._wall = WallClockEvaluator(
            self._make_step, warmup=warmup, iters=iters, adaptive=adaptive,
            rel_halfwidth=rel_halfwidth)

    def _make_step(self, point: Dict):
        return self.spec.build(self.shape, point)

    def __call__(self, point: Dict,
                 fidelity: Optional[float] = None) -> Tuple[float, dict]:
        host = {k: point[k] for k in HOST_KNOBS if k in point}
        tile = {k: v for k, v in point.items() if k not in HOST_KNOBS}
        if host:
            if not self.allow_subprocess:
                raise ValueError(
                    f"point carries host knobs {sorted(host)} but this "
                    "evaluator was built with allow_subprocess=False — "
                    "XLA_FLAGS cannot change inside a live process; build "
                    "KernelTuneEvaluator(..., allow_subprocess=True)")
            return self._call_subprocess(tile, host, fidelity)
        try:
            value, meta = self._wall(tile, fidelity=fidelity)
        except ValueError:
            raise  # point-hygiene errors must surface, not score -inf
        except Exception as e:  # an infeasible tile config = failed run
            return -math.inf, {"error": f"{type(e).__name__}: {e}"}
        return value, dict(meta, kernel=self.kernel)

    # -- subprocess harness (host knobs) -------------------------------------
    def _call_subprocess(self, tile: Dict, host: Dict,
                         fidelity: Optional[float]) -> Tuple[float, dict]:
        refuse_child_on_tpu("measuring host knobs")
        payload = {"kernel": self.kernel, "shape": self.shape, "point": tile,
                   "fidelity": fidelity, **self._harness}
        env = dict(os.environ)
        flags = []
        if "host_devices" in host:
            flags.append("--xla_force_host_platform_device_count="
                         f"{int(host['host_devices'])}")
        if host.get("xla_flags"):
            flags.append(str(host["xla_flags"]))
        if flags:
            env["XLA_FLAGS"] = " ".join(flags)
        src = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-m", "repro.tuning.kernel_objective",
             json.dumps(payload)],
            capture_output=True, text=True, env=env, timeout=self.timeout)
        if proc.returncode != 0:
            return -math.inf, {"error": proc.stderr.strip()[-2000:],
                               "kernel": self.kernel, "host": host}
        out = json.loads(proc.stdout.strip().splitlines()[-1])
        return float(out["value"]), dict(out["meta"], host=host)


def main(argv=None) -> int:
    """Subprocess entry: measure one payload, print one JSON line.

    ``python -m repro.tuning.kernel_objective '<payload json>'`` where
    payload = {kernel, shape, point, fidelity, warmup, iters, adaptive,
    rel_halfwidth}.  XLA_FLAGS/host knobs are the *caller's* job (set in
    this process's environment before jax is imported — which is why
    this module defers every jax import into the builders).
    """
    argv = sys.argv[1:] if argv is None else argv
    payload = json.loads(argv[0])
    ev = KernelTuneEvaluator(
        payload["kernel"], payload.get("shape"),
        warmup=int(payload.get("warmup", 1)),
        iters=int(payload.get("iters", 3)),
        adaptive=bool(payload.get("adaptive", True)),
        rel_halfwidth=float(payload.get("rel_halfwidth", 0.2)),
    )
    value, meta = ev(payload.get("point") or {},
                     fidelity=payload.get("fidelity"))
    print(json.dumps({"value": value, "meta": meta}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
