"""The system under test, as the benchmark drives it: the program's
``ServingEngine`` behind its ``GenerateService``, built from a
configuration file with weights the benchmark makes from the seed.

This is the only module of the benchmark that imports the program.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from harness import check, files
from repro.launch.mesh import device_mesh
from repro.models import params as pm
from repro.models.config import ModelConfig
from repro.models.transformer import param_specs
from repro.serve.engine import EngineConfig, EngineStats, ServingEngine

# A CPU rehearsal serves the program's own tiny sibling of the model with
# interpreted kernels and short sequences; it checks the harness's paths,
# never a device number.
REHEARSAL_ENGINE = {"s_max": 256, "buckets": [1, 2, 4, 8],
                    "prefill_chunks": [16, 64], "n_kv_blocks": None,
                    "n_dense_slots": 8, "kernel_backend": "pallas-interpret"}

SIZE_KEYS = ("family", "d_model", "n_layers", "n_heads", "n_kv_heads",
             "d_ff", "vocab_size", "qkv_bias", "rope_theta", "d_inner",
             "ssm_heads", "ssm_headdim", "ssm_state", "ssm_groups",
             "conv_kernel")


def model_config(conf: dict, rehearse: bool) -> ModelConfig:
    m = dict(conf["model"])
    dt = jnp.dtype(m.pop("dtype"))
    m.pop("rms_norm_eps")
    m.pop("initializer_range", None)
    if "layer_pattern" in m:
        m["layer_pattern"] = tuple(tuple(p) for p in m["layer_pattern"])
    cfg = ModelConfig(name=conf["name"], param_dtype=dt, compute_dtype=dt,
                      **m)
    if rehearse:
        from repro.configs.registry import reduced
        cfg = reduced(cfg)
    return cfg


def sizes(cfg: ModelConfig, conf: dict, rehearse: bool = False) -> dict:
    """The sizes the reference and the FLOP counts read: those of the model
    actually served."""
    s = {k: getattr(cfg, k) for k in SIZE_KEYS}
    s.update(head_dim=cfg.hd(), rms_norm_eps=conf["model"]["rms_norm_eps"],
             dtype=jnp.dtype(cfg.param_dtype).name)
    init = conf.get("rehearsal", {}) if rehearse else conf["model"]
    if "initializer_range" in init:
        s["initializer_range"] = init["initializer_range"]
    return s


def engine_config(conf: dict, rehearse: bool) -> EngineConfig:
    ec = dict(conf["engine"])
    if rehearse:
        ec.update({k: v for k, v in REHEARSAL_ENGINE.items()
                   if k in ec or k == "kernel_backend"})
    for k in ("buckets", "prefill_chunks"):
        ec[k] = tuple(ec[k])
    return EngineConfig(**ec)


def make_weights(conf: dict, cfg: ModelConfig, s: dict, mesh, seed: int):
    """The served weights in the program's layout, made on the device by
    one jitted call from the seed (layout checked leaf by leaf)."""
    ref = files.module("reference", conf["reference"])
    lay = files.module("layouts", conf["reference"])
    specs = param_specs(cfg, 1, 1, preskew=False)
    is_spec = lambda x: isinstance(x, pm.ParamSpec)
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp.pspec),
                             specs, is_leaf=is_spec)
    make = jax.jit(lambda k: lay.to_program(s, ref.init_weights(s, k)),
                   out_shardings=shardings)
    params = make(check.weights_key(seed))

    def same(a, sp):
        if a.shape != sp.shape or a.dtype != jnp.dtype(sp.dtype):
            raise ValueError(f"layout gives {a.shape} {a.dtype}, the "
                             f"program stores {sp.shape} {sp.dtype}")
    jax.tree.map(same, params, specs, is_leaf=is_spec)
    return params


def build(conf: dict, cfg: ModelConfig, chips: int, params,
          rehearse: bool) -> ServingEngine:
    mesh, plan = device_mesh(jax.devices()[:chips])
    return ServingEngine(cfg, mesh, plan, params=params,
                         engine_cfg=engine_config(conf, rehearse))


def mesh_for(chips: int):
    return device_mesh(jax.devices()[:chips])[0]


def executables(eng: ServingEngine):
    """Every (bucket, chunk) step program the engine can launch; chunk 0 is
    the one-position decode step."""
    ec = eng.engine_cfg
    return [(b, c) for b in ec.buckets
            for c in (0,) + eng.prefill_chunk_ladder]


def warm_up(eng: ServingEngine) -> None:
    """Compile (or load from the persistent cache) and run once every step
    program, with idle slots, then serve one short request end to end so
    that the host-side helpers compile too; counters start from zero."""
    T = eng.engine_cfg.s_max // eng.engine_cfg.block_pos_stride
    vec = lambda a: jax.device_put(jnp.asarray(a), eng._vec_sharding)
    mat = lambda a: jax.device_put(jnp.asarray(a), eng._table_sharding)
    for b, c in executables(eng):
        ops = ([mat(np.full((b, T), -1, np.int32))]
               if eng.store.needs_pages else []) \
            + ([vec(np.full((b,), -1, np.int32))]
               if eng.store.has_dense else [])
        zeros = np.zeros((b,), np.int32)
        if c == 0:
            k, head = eng._kernel(b), [vec(zeros), vec(zeros)]
        else:
            k = eng._chunk_kernel(b, c)
            head = [mat(np.zeros((b, c), np.int32)), vec(zeros), vec(zeros)]
        logits, eng.store.arena = eng.queue.enqueue(
            k, eng.params, eng.store.arena, *head, *ops)
        np.asarray(logits[:, 0, :eng.cfg.vocab_size])
        eng.queue.finish()
    stride = eng.engine_cfg.block_pos_stride
    prompt = [(7 * i) % eng.cfg.vocab_size for i in range(3 * stride + 5)]
    eng.submit(prompt)
    eng.drain()
    eng.stats = EngineStats()


def stats(eng: ServingEngine) -> dict:
    d = dataclasses.asdict(eng.stats)
    d["launches"] = eng.stats.launches
    return d


class LaunchLog:
    """Spans around the calls into each layer, in the profiler's own trace
    (so they share its clock with the device), and one record per launch
    of what it fed.  Installed on one engine only for a traced run."""

    def __init__(self, eng: ServingEngine):
        self.launches = []
        step, launch, commit = eng.step, eng._launch, eng._commit
        schedule = eng.scheduler.schedule

        def _step():
            with TraceAnnotation("bench/step"):
                return step()

        def _schedule():
            with TraceAnnotation("bench/schedule"):
                return schedule()

        def _launch(sd, chunk):
            slots = []
            for r in sd.slots:
                if r is None:
                    continue
                fed = 1 if chunk is None else eng._fed_count(r, chunk)
                slots.append((r.num_cached, fed,
                              r.num_cached + fed == len(r.seq_tokens)))
            kind = "decode" if chunk is None else "prefill"
            i = len(self.launches)
            self.launches.append({"seq": i, "kind": kind,
                                  "bucket": sd.bucket, "chunk": chunk or 1,
                                  "slots": slots})
            with TraceAnnotation(f"bench/launch/{kind}", seq=i):
                return launch(sd, chunk)

        def _commit(*a, **kw):
            with TraceAnnotation("bench/commit"):
                return commit(*a, **kw)

        eng.step, eng._launch, eng._commit = _step, _launch, _commit
        eng.scheduler.schedule = _schedule
