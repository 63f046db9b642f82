#!/usr/bin/env python3
"""The one process that holds the chip.

Started by ``run.py`` (which never touches jax). It makes the seeded
weights, has the configuration's check module (``checks/<check.kind>.py``)
evaluate the plain reference on the seeded sample, writes the weights
as ``weights.msgpack`` into a temporary model repository, DROPS every
device buffer of its own, stands the server up through the code
``python -m triton_client_tpu serve`` runs (argv parser ->
``build_server`` -> ``start``), compiles every launch shape the cell's
traffic can form, and then answers the parent's commands, one JSON
object a line on the stream it was given as stdout (everything else
this process prints goes to stderr):

    -> {"ready": ...}                      after set-up
    <- {"cmd": "profile", "seconds": s, "after_s": a}   trace the device for s seconds, a seconds from now
    <- {"cmd": "finish"}                   drain, report, exit
    -> {"done": ...}
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import pathlib
import sys
import threading
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmarks import loadgen  # noqa: E402


# the server's ring of request traces in a traced run: read once the window is over, it still has to hold the
# window's FIRST requests (16,448 a window in the sessions cell, whose spans are read before its trace begins)
TRACE_CAPACITY = 32768


def seeded(seed: int, stream: int) -> np.random.Generator:
    """Stream 0: calibration input; 1: the sample and request pool;
    2: the warm-up's arrivals and order; 3: the measured window's (its
    own, so that a longer warm-up does not change what the window
    sends). ``run.py`` draws the same streams."""
    return np.random.default_rng([int(seed), stream])


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def apply_rehearsal(cfg: dict) -> dict:
    """Tiny sizes for a CPU rehearsal: the configuration's own
    ``rehearsal`` block overrides ``model``, ``weights`` and ``check``
    keys (never used on a chip)."""
    cfg = json.loads(json.dumps(cfg))
    for block in ("model", "weights", "check"):
        for key, value in cfg["rehearsal"].get(block, {}).items():
            if isinstance(value, dict):
                cfg[block][key] = {**cfg[block][key], **value}
            else:
                cfg[block][key] = value
    return cfg


def rehearsal_traffic(traffic: dict, cfg: dict) -> dict:
    """The traffic mix at a CPU rehearsal's sizes: the configuration's
    ``rehearsal.traffic`` block overrides the mix's own keys (a batch
    that fills a quarter of a chip is minutes of interpreted kernels)."""
    return {**traffic, **cfg["rehearsal"].get("traffic", {})}


def input_params(traffic: dict, cfg: dict, rehearse: bool) -> dict:
    """The traffic mix's input parameters (a rehearsal shrinks them)."""
    params = traffic["inputs"]["params"]
    if rehearse:
        params = {**params, **cfg["rehearsal"].get("traffic_params", {})}
    return params


def sample_size(cfg: dict, traffic: dict, rehearse: bool) -> int:
    """How many draws the output check's sample (and the window's pool)
    asks of the input generator: requests, or streams where the mix
    sends streams. The mix's ``sample_requests`` where it states one,
    else as many requests as hold ``check.sample_items`` items."""
    n = int(traffic.get("sample_requests") or max(1, cfg["check"]["sample_items"] // traffic["items_per_request"]))
    if rehearse:
        n = min(n, cfg["rehearsal"].get("sample_requests", 4))
    return n


def check_module(cfg: dict):
    """What the configuration's family decides (``checks/<kind>.py``)."""
    return importlib.import_module(f"benchmarks.checks.{cfg['check']['kind']}")


def entry_doc(cfg: dict, rehearse: bool, precision: str | None) -> dict:
    """The committed entry's config.yaml as served: what the check
    module makes of it (paths, a rehearsal's sizes), then the output
    check's control (a lower precision) and the configuration's batch."""
    from triton_client_tpu.dataset_config import load_yaml

    doc = check_module(cfg).entry(load_yaml(str(ROOT / cfg["entry"] / "config.yaml")), cfg, rehearse)
    if precision:
        doc["model"] = {**dict(doc.get("model", {})), "precision": precision}
    doc["max_batch_size"] = int(cfg["max_batch_size"])  # the configuration's, where it departs from the entry's
    return doc


def calibration_input(generator, traffic: dict, params: dict, cfg: dict, seed: int) -> dict | None:
    """The seeded input the weights' batch-norm statistics are taken
    on: ``weights.calibration_items`` items of the cell's own traffic
    (``weights.calibration_params`` overrides the mix's input
    parameters, so that eight frames are not cut from 768 drawn). None
    where the configuration states no such count: its weights are drawn
    from the key alone."""
    items = int(cfg["weights"].get("calibration_items", 0))
    if not items:
        return None
    params = {**params, **cfg["weights"].get("calibration_params", {})}
    made = generator.make(seeded(seed, 0), items, params, cfg)  # at most one item a request too many
    return loadgen.first_items(loadgen.stacked(made, cfg), items)


def make_weights(reference, cfg: dict, seed: int, calibration: dict):
    """Seeded weights on the device, in one jitted call whose program
    does not depend on the seed (so the compile cache holds it)."""
    import jax

    key = jax.random.wrap_key_data(
        np.asarray([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    )
    return jax.jit(lambda k, c: reference.init_params(k, c, cfg))(key, calibration)


def write_msgpack(path: pathlib.Path, tree) -> None:
    """``flax.serialization.to_bytes(tree)`` written leaf by leaf, byte
    for byte the same file: msgpack is compositional, so a map is its
    header and then each key and value. One leaf at a time is on the
    host, not the whole tree beside a ``bytes`` of the whole tree."""
    import flax.serialization
    import msgpack

    packer = msgpack.Packer()

    def put(f, node) -> None:
        if isinstance(node, dict):
            f.write(packer.pack_map_header(len(node)))
            for key in sorted(node):  # the order jax's tree_map gave the whole-tree copy
                f.write(packer.pack(key))
                put(f, node[key])
        else:
            f.write(flax.serialization.msgpack_serialize(np.asarray(node)))

    with open(path, "wb") as f:
        put(f, flax.serialization.to_state_dict(tree))


def write_repository(root: pathlib.Path, cfg: dict, tree, rehearse: bool,
                     precision: str | None = None) -> str:
    """``<root>/<entry name>/config.yaml`` + ``1/weights.msgpack``: the
    program's normal loading path. Returns the served model's name.
    Without a ``tree`` the entry has no weights and loads at its own
    initialisation (``program_temp_bytes``)."""
    import yaml

    name = pathlib.Path(cfg["entry"]).name
    version = root / name / "1"
    version.mkdir(parents=True)
    with open(root / name / "config.yaml", "w") as f:
        yaml.safe_dump(entry_doc(cfg, rehearse, precision), f, sort_keys=False)
    if tree is not None:
        write_msgpack(version / "weights.msgpack", tree)
    return name


@contextlib.contextmanager
def cache_subdirectory(cache_dir: str, name: str):
    """Compile inside ``<cache_dir>/<name>`` (no-op where the cache is off)."""
    if not cache_dir:
        yield
        return
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    sub = os.path.join(cache_dir, name)
    os.makedirs(sub, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", sub)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()


def start_server(repo: pathlib.Path, argv: list[str]):
    from triton_client_tpu.cli import serve

    full = ["-r", str(repo), "-a", "127.0.0.1:0", "--metrics-port", "auto", *argv]
    print("serve " + " ".join(full), flush=True)
    args = serve.make_parser().parse_args(full)
    server = serve.build_server(args)
    server.start()
    return server, args


def device_channel(server):
    channel = server.channel
    while hasattr(channel, "inner"):
        channel = channel.inner
    return channel


def served_model(server, name: str):
    """The model as the server registered it, read from the device
    channel's repository (the program has no public handle on it)."""
    return device_channel(server)._repository.get(name)


def compile_launch_shapes(server, name: str, launches: list[dict]) -> float:
    """Compile every launch shape the batcher can form for this cell:
    the check module's ``launch_request`` for each of the mix's
    ``launch_batch_sizes``, sent straight to the device channel under
    the batcher (the front door cannot ask for a merge size). The
    launches run side by side so the compiles do."""
    from triton_client_tpu.channel.base import InferRequest

    channel = device_channel(server)
    t0 = time.perf_counter()
    errors = []

    def launch(k: int, inputs: dict) -> None:
        try:
            channel.do_inference(InferRequest(name, inputs))
        except Exception as e:  # reported, then raised on the main thread
            errors.append(f"launch shape {k}: {e!r}")

    threads = [threading.Thread(target=launch, args=(k, inputs)) for k, inputs in enumerate(launches)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError("; ".join(errors))
    return time.perf_counter() - t0


def launch_temp_bytes(model, shapes: dict) -> int:
    """XLA's statement of one launch's temporaries for a built model's
    device program. Where the model carries its weights as ``params``
    (``device_fn(inputs, params)``) they are lowered ABSTRACT, as
    shapes and types: no second tree on the device and no gigabytes of
    constants in the module. A model without ``params`` has them as
    closure constants of ``device_fn(inputs)``."""
    import jax

    program = jax.jit(model.device_fn)
    if model.params is None:
        lowered = program.lower(shapes)
    else:
        abstract = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), model.params)
        lowered = program.lower(shapes, abstract)
    return int(lowered.compile().memory_analysis().temp_size_in_bytes)


def program_temp_bytes(root: pathlib.Path, cfg: dict, launches: list[dict], served, kept_dir: str) -> int:
    """The temporaries of the served device program at the cell's
    largest launch shape, as XLA states them for this chip
    (``memory_analysis().temp_size_in_bytes``). The allocator's
    ``peak_bytes_in_use`` leaves a running program's temporaries out
    (PERF.md section 2: a b8 launch raised it by 19 MB where one
    layer's activations are 33 MB), so a chip's peak is that statistic
    plus this. ``served`` is the model the server registered. Where it
    carries ``params`` its own device program is lowered with them
    abstract (``launch_temp_bytes``) and nothing is built. Where it
    does not, the entry is built once more by the program's own loading
    path at its own initialisation: buffer sizes follow from shapes,
    not from weights, and a program that does not change with the seed
    stays in the compile cache (such weights are constants of the
    module, so they are megabytes). Building the entry and loading that
    program took 21 s of every set-up, so the number is kept beside the
    compile cache under a key of everything it can depend on: the
    configuration, the shapes, the chip, the installation and every
    source file of the program."""
    import hashlib

    import jax
    import jaxlib
    from triton_client_tpu.runtime.disk_repository import build_model

    shapes = [{k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in launch.items()} for launch in launches]
    digest = hashlib.sha256(json.dumps(
        [cfg, [{k: (v.shape, str(v.dtype)) for k, v in launch.items()} for launch in shapes],
         jax.devices()[0].device_kind, jax.__version__, jaxlib.__version__], sort_keys=True, default=str).encode())
    for source in sorted((ROOT / "triton_client_tpu").rglob("*.py")):
        digest.update(source.read_bytes())
    kept = pathlib.Path(kept_dir) / f"program_temp_{digest.hexdigest()[:24]}.json" if kept_dir else None
    if kept is not None and kept.exists():
        return int(load_json(kept)["temp_size_in_bytes"])

    model = served
    if served.params is None:
        argv = cfg["serve_argv"]
        precision = argv[argv.index("--precision") + 1] if "--precision" in argv else None
        model = build_model(root / write_repository(root, cfg, None, False, precision))
    temp = max(launch_temp_bytes(model, launch) for launch in shapes)
    if kept is not None:
        kept.parent.mkdir(parents=True, exist_ok=True)
        kept.write_text(json.dumps({"temp_size_in_bytes": temp, "config": cfg["name"],
                                    "weights": "closure constants" if served.params is None else "abstract"}))
    return temp


def bytes_on_device(devices, key: str) -> int:
    """The allocator's ``key`` on the fullest of ``devices`` (0 where
    the backend keeps no statistics)."""
    return max((int((d.memory_stats() or {}).get(key, 0)) for d in devices), default=0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--work", required=True)
    p.add_argument("--chips", type=int, default=1)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--record-trace", default="")
    args = p.parse_args(argv)

    # the protocol owns the original stdout; every other print -> stderr
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    def say(obj) -> None:
        proto.write(json.dumps(obj) + "\n")
        proto.flush()

    from triton_client_tpu.utils.compilation_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()  # <checkout>/.jax_cache unless set outside
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if not args.rehearse and (device["platform"] != "tpu" or len(devices) < args.chips):
        print(f"benchmark needs {args.chips} TPU chip(s); jax found {device}", file=sys.stderr)
        return 3
    marks = {"jax_s": time.perf_counter() - T0}

    cfg = load_json(ROOT / args.config)
    traffic = load_json(ROOT / args.traffic)
    if args.rehearse:
        cfg = apply_rehearsal(cfg)
        traffic = rehearsal_traffic(traffic, cfg)
    reference = importlib.import_module(f"benchmarks.references.{cfg['reference']}")
    generator = importlib.import_module(f"benchmarks.inputs.{traffic['inputs']['generator']}")
    params = input_params(traffic, cfg, args.rehearse)

    check = check_module(cfg)
    work = pathlib.Path(args.work)
    sample = generator.make(seeded(args.seed, 1), sample_size(cfg, traffic, args.rehearse), params, cfg)
    first = loadgen.first_request(sample)
    launches = [check.launch_request(first, b) for b in traffic["launch_batch_sizes"]]
    # the benchmark's own two programs do not depend on the seed; they
    # live in a subdirectory of the cache, where the served launchers
    # (new constants, so new entries, with every seed) cannot evict them
    with cache_subdirectory(cache_dir, "benchmark"):
        calibration = calibration_input(generator, traffic, params, cfg, args.seed)
        tree = make_weights(reference, cfg, args.seed, calibration)
        marks["weights_s"] = time.perf_counter() - T0
        ref_stats = check.expected(reference, cfg, tree, sample, work / "reference.npz")
        marks["reference_s"] = time.perf_counter() - T0
    name = write_repository(work / "repo", cfg, tree, args.rehearse)
    # the weights are on the device once: the yardstick lets go of everything it holds there (the
    # tree, the calibration input, the reference's programs and outputs) before the server loads them
    del tree, calibration
    gc.collect()
    jax.clear_caches()
    before = {"bytes_in_use_before_server": bytes_on_device(devices[: args.chips], "bytes_in_use"),
              "peak_bytes_before_server": bytes_on_device(devices[: args.chips], "peak_bytes_in_use")}
    marks.update(before)
    print(json.dumps(before), flush=True)

    trace_argv = ["--trace-capacity", str(TRACE_CAPACITY) if args.trace else "0"]
    server, serve_args = start_server(work / "repo", [*cfg["serve_argv"], *trace_argv])
    marks["server_s"] = time.perf_counter() - T0
    with cache_subdirectory(cache_dir, "benchmark"):
        temp_bytes = 0 if args.rehearse else program_temp_bytes(
            work / "shapes", cfg, launches, served_model(server, name),
            os.path.join(cache_dir, "benchmark") if cache_dir else "")
    marks["program_temp_s"] = time.perf_counter() - T0

    written = []  # the persistent cache records a miss when it writes a newly compiled program
    jax.monitoring.register_event_listener(
        lambda event, **kw: written.append(event) if event.endswith("/cache_misses") else None
    )
    compile_s = compile_launch_shapes(server, name, launches)
    del launches
    marks["compiled_s"] = time.perf_counter() - T0

    say({
        "ready": True, "device": device, "port": server.port,
        "metrics_port": server.metrics_port, "model": name,
        "reference": str(work / "reference.npz"), "reference_stats": ref_stats,
        "compile_cache_dir": cache_dir or "off", "launch_compile_s": compile_s,
        "compiled_at_unix": time.time(), "compiled_anew": bool(written) or not cache_dir,
        "marks": marks,
    })

    traced = False
    log_dir = work / "profile"
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "profile":
            # the device's lines only. With the host tracer at level 1
            # (let alone the Python tracer) the host's staging work
            # slowed 60-fold while traced (XlaLinearize of a b8 uint8
            # batch 300 ms for 5) and the device read 96% idle for 34%
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            options.enable_hlo_proto = False
            time.sleep(float(cmd.get("after_s", 0.0)))  # past the callers' start, where the mix says so
            started = time.perf_counter()  # the clock the load generator's window and the server's spans are on
            jax.profiler.start_trace(str(log_dir), profiler_options=options)
            time.sleep(float(cmd["seconds"]))
            jax.profiler.stop_trace()
            traced = True
            say({"profiled": True, "started_perf_counter_s": started})
        elif cmd["cmd"] == "finish":
            break

    # the reduction is Python and holds this process's interpreter lock
    # for seconds: it waits for the window to end, or the server's own
    # threads starve (a traced replay fell from 106 to 18 scans/s)
    profile = None
    if traced:
        from benchmarks import trace_reduce

        profile = trace_reduce.reduce_dir(log_dir, args.chips, args.record_trace or None)
    peak = bytes_on_device(devices[: args.chips], "peak_bytes_in_use")
    drained = server.drain(timeout_s=serve_args.drain_timeout)
    # the allocator's peak is the process's and never falls: where it has not risen since the server
    # started, the yardstick's own buffers (the reference, the second copy of the weights) set it
    phase = "not stated" if not peak else "server" if peak > marks["peak_bytes_before_server"] else "yardstick"
    say({"done": True, "memory_peak_bytes": peak + temp_bytes, "memory_buffers_peak_bytes": peak,
         "memory_program_temp_bytes": temp_bytes, "memory_peak_before_server_bytes": marks["peak_bytes_before_server"],
         "memory_peak_phase": phase, "profile": profile, "drained": bool(drained)})
    return 0


if __name__ == "__main__":
    sys.exit(main())
