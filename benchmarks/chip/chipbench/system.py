"""The system under test: the program's serving engine, built through its
own entry points from a configuration file, a traffic mix and a seed.

``launch/serve.build_parser`` and ``config_from_args`` give the model
configuration (the configuration's layout sets what the file fixes, such
as its depth, and the program must match every width the file states),
``build_model`` / ``param_shardings`` the model and its sharding,
``engine_config_for`` the engine's shapes, ``ServeEngine`` the engine.
The weights are the benchmark's (the layout's ``fill_program_tree``),
made on the device in one jitted call from the seed.

``Recorder`` wraps the engine's two jitted step entries and its finish
hook from outside: it records what each call was given (positions, the
active mask, the chunk's span) and every finished request's tokens;
around each dispatch it opens a ``bench.prefill`` / ``bench.decode``
profiler span.  In a traced run it also turns on the decode step's
per-layer expert loads (a diagnostic output the program offers, which
the engine turns on only for tiered residency), so the timed runs
(``--trace 0``) run the decode program users run.  These hooks are the
program's private names (``StepCore.prefill_fn``, ``decode_fn`` and
``layer_diags``, ``ServeEngine._finish``); a program without them is
refused by name.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List

import jax
import numpy as np

from chipbench import traffic, weights


def serve_argv(cfg_doc: Dict[str, Any], mix: Dict[str, Any],
               seed32: int) -> List[str]:
    dep = cfg_doc["deployment"]
    max_prompt, max_out = traffic.max_lengths(mix)
    argv = ["--arch", cfg_doc["program"]["arch"],
            *(["--reduced"] if cfg_doc["program"].get("reduced") else []),
            "--batch", str(dep["max_slots"]),
            "--prompt-len", str(max_prompt), "--gen", str(max_out),
            "--policy", dep["moe_policy"],
            "--model-par", str(dep["model_par"]),
            "--paged", "--kv-block-size", str(dep["kv_block_size"]),
            "--kv-blocks", str(dep["kv_blocks"]),
            "--prefill-chunk", str(dep["prefill_chunk"]),
            "--seed", str(seed32)]
    if mix.get("router_skew", 0.0) > 0:
        argv += ["--skew", str(mix["router_skew"])]
    if dep["fused_attention"]:
        argv.append("--fused-attention")
    if dep["fused_moe"]:
        argv.append("--fused-moe")
    return argv


def model_config(cfg_doc: Dict[str, Any], layout, args):
    """The program's configuration for ``cfg_doc`` and the widths both
    state: the registered arch as ``layout.program_config`` sets it; every
    width the layout reads from the program must equal the file's (those
    in ``layout.FROM_FILE`` are the file's alone)."""
    from repro.launch.serve import config_from_args
    want = layout.from_config(cfg_doc)
    cfg = layout.program_config(config_from_args(args), want)
    got = dataclasses.replace(
        layout.from_program(cfg),
        **{f: getattr(want, f) for f in layout.FROM_FILE})
    if got != want:
        diff = ", ".join(
            f"{f.name}: program {getattr(got, f.name)!r}, file "
            f"{getattr(want, f.name)!r}" for f in dataclasses.fields(want)
            if getattr(got, f.name) != getattr(want, f.name))
        raise ValueError(f"the program's {cfg.name} differs from the "
                         f"configuration file ({diff})")
    return cfg, want


@dataclass
class Call:
    phase: str                    # "prefill" | "decode"
    t: float                      # host perf_counter at dispatch
    rows: List[tuple]             # (queries, context) per live row
    traced: bool                  # issued while the profiler ran
    loads: Any = None             # [layers, experts] routed units (decode)


@dataclass
class Recorder:
    calls: List[Call] = field(default_factory=list)
    outputs: Dict[int, List[int]] = field(default_factory=dict)
    tracing: bool = False

    def attach(self, engine, diags: bool) -> None:
        core = engine.core
        for obj, name in ((core, "prefill_fn"), (core, "decode_fn"),
                          (core, "layer_diags"), (engine, "_finish")):
            if not hasattr(obj, name):
                raise AttributeError(
                    f"the program's {type(obj).__name__} has no {name!r}, "
                    f"which the benchmark's recorder wraps")
        prefill_fn, decode_fn = core.prefill_fn, core.decode_fn
        core.layer_diags = diags

        def prefill(params, chunk, scratch, start, last, *rest):
            n = int(last) + 1
            self.calls.append(Call("prefill", time.perf_counter(),
                                   [(n, int(start) + n)], self.tracing))
            with jax.profiler.TraceAnnotation("bench.prefill"):
                return prefill_fn(params, chunk, scratch, start, last, *rest)

        def decode(params, tok, pool, pos, *rest):
            active = np.asarray(rest[2])
            ctx = np.asarray(pos)[active] + tok.shape[1]
            call = Call("decode", time.perf_counter(),
                        [(tok.shape[1], int(c)) for c in ctx], self.tracing)
            self.calls.append(call)
            with jax.profiler.TraceAnnotation("bench.decode"):
                out = decode_fn(params, tok, pool, pos, *rest)
            if diags:
                if "expert_load_layers" not in out[2]:
                    raise KeyError("the decode step returned no "
                                   "'expert_load_layers' with layer_diags on")
                call.loads = out[2]["expert_load_layers"]
            return out

        # the engine reads its jit caches' sizes through these
        prefill._cache_size = prefill_fn._cache_size
        decode._cache_size = decode_fn._cache_size
        core.prefill_fn, core.decode_fn = prefill, decode
        finish = engine._finish

        def capture(st, now):
            self.outputs[st.req.rid] = list(st.output)
            finish(st, now)
        engine._finish = capture

    def clear(self) -> None:
        self.calls.clear()
        self.outputs.clear()


@dataclass
class System:
    dims: Any                     # the layout's Dims
    engine: Any                   # the program's ServeEngine
    recorder: Recorder


def build(cfg_doc: Dict[str, Any], layout, mix: Dict[str, Any], seed: int,
          diags: bool = False) -> System:
    """The engine with its recorder, its weights filled by ``layout`` (the
    configuration's ``layouts/<name>.py``); ``diags`` turns on the decode
    step's per-layer expert loads (traced runs)."""
    from repro.configs.base import ParallelConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import build_parser, engine_overrides
    from repro.launch.shardings import param_shardings
    from repro.models.model import MeshShape, build_model
    from repro.serve import ServeEngine, engine_config_for

    seed32 = int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
                 & 0x7FFFFFFF)
    args = build_parser().parse_args(serve_argv(cfg_doc, mix, seed32))
    cfg, dims = model_config(cfg_doc, layout, args)
    dep = cfg_doc["deployment"]
    pcfg = ParallelConfig(attn_chunk=min(512, args.prompt_len))
    mesh = make_host_mesh(data=1, model=dep["model_par"])
    ms = MeshShape(tuple(zip(mesh.axis_names, mesh.devices.shape)))
    model = build_model(cfg, pcfg, batch=args.batch, seq_len=args.prompt_len,
                        mesh_shape=ms, mesh=mesh)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shardings = param_shardings(shapes, cfg, pcfg, mesh)
    fill = jax.jit(lambda k: layout.fill_program_tree(
        shapes, dims, dep["model_par"], k), out_shardings=shardings)
    params = fill(weights.seed_key(seed))
    ecfg = engine_config_for(
        cfg, max_slots=args.batch, prompt_len=args.prompt_len,
        max_new_tokens=args.gen, skew_seed=seed32, role="unified",
        moe_policy=None, **engine_overrides(args))
    engine = ServeEngine(model, params, ecfg, mesh=mesh)
    rec = Recorder()
    rec.attach(engine, diags)
    return System(dims=dims, engine=engine, recorder=rec)


def requests(specs: List[traffic.Spec]):
    from repro.serve import Request
    return [Request(rid=s.rid, tokens=s.prompt, max_new_tokens=s.output_len,
                    arrival_time=s.arrival) for s in specs]
