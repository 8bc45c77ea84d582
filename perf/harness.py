"""What every cell shares: the run's flow, the sink, the checks, the result line.

Nothing here names a cell, a configuration, a program, an architecture or
a metric. A cell is found by its name in ``BENCHMARK.json``; its
configuration is ``configs/<config>.json``, whose ``program`` names the
module ``programs/<program>.py`` and whose ``reference`` the module
``reference/<reference>.py`` (see ``config_module``); its traffic mix is
``traffic/<traffic>.json``, whose ``kind`` names the module
``traffic/<kind>.py``; a per-layer metric is ``layer_metrics/<metric>.json``
(see ``layer_metric_spec``), whose ``reducer`` names ``reducers/<reducer>.py``.
See ``perf/README.md``.
"""

from __future__ import annotations

import collections
import importlib
import json
import logging
import os
import shutil
import statistics
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)

# The jax.monitoring events that mean "a program was lowered, compiled or
# fetched from the cache": none may fire inside the measured window.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)


def read_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(PERF_DIR, *parts)) as f:
        return json.load(f)


def layer_metric_spec(name: str) -> Dict[str, Any]:
    """``layer_metrics/<name>.json``. A quantity that the manifest splits by
    the end-to-end metric it moves (``<reading>.<cells>``) is one reading
    with one file, ``layer_metrics/<reading>.json``."""
    for stem in (name, name.rsplit(".", 1)[0]):
        if os.path.isfile(os.path.join(PERF_DIR, "layer_metrics", f"{stem}.json")):
            return read_json("layer_metrics", f"{stem}.json")
    raise FileNotFoundError(f"perf/layer_metrics/{name}.json")


def load_module(kind: str, name: str):
    """``perf/<kind>/<name>.py``, found by name."""
    return importlib.import_module(f"perf.{kind}.{name}")


def config_module(config: Dict[str, Any], key: str):
    """The module that a configuration's file names under ``key``: its
    ``program`` (``perf/programs/<name>.py``, the system under test) or its
    ``reference`` (``perf/reference/<name>.py``, the architecture's plain
    reference). There is no default: a configuration that names none, or
    one that is not there, ends the run as a missing chip does."""
    folder = {"program": "programs", "reference": "reference"}[key]
    name = config.get(key)
    module = None
    if isinstance(name, str) and name:
        try:
            module = load_module(folder, name)
        except ModuleNotFoundError as e:
            if e.name != f"perf.{folder}.{name}":
                raise  # the module is there; something it imports is not
    if module is None:
        print(
            f"perf: the configuration's file names no {key} that exists "
            f"({key!r}: {name!r}; wanted perf/{folder}/<name>.py). "
            "No result is printed.",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return module


def first_steps_module():
    """What every architecture's reference shares: Adam, the number of
    first steps, the first steps themselves and the worst-leaf arithmetic."""
    return load_module("reference", "first_steps")


def say(label: str, **fields: Any) -> None:
    print(f"perf {label}: {json.dumps(fields, sort_keys=True, default=str)}", flush=True)


class SpanLog:
    """A ``MetricsSink`` that keeps every span (with its kind: phase, wait
    or work) and counter with the time it ended on this process's monotonic
    clock. Registered only in a traced run."""

    def __init__(self) -> None:
        self.spans: List[Dict[str, Any]] = []
        self.counters: List[Dict[str, Any]] = []

    def on_span_record(self, record) -> None:
        end = time.monotonic()
        self.spans.append(
            {"name": record.name, "start": end - record.duration_s, "end": end,
             "bytes": int(record.attrs.get("bytes", 0) or 0), "kind": record.kind}
        )

    def on_counter(self, name, delta, value) -> None:
        self.counters.append({"name": name, "t": time.monotonic(), "delta": delta})

    def __getattr__(self, name):  # the sink's other callbacks: nothing to keep
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)


class WarningTrap(logging.Handler):
    """Every ``tpusnap`` log record at WARNING or above: the library's quiet
    fallbacks all announce themselves there."""

    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.messages: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        line = f"{record.levelname} {record.name}: {record.getMessage()}"
        self.messages.append(line)
        print(f"perf: tpusnap logged {line}", file=sys.stderr, flush=True)


class Tracer:
    """Profiles one short slice of the window; the traffic says when."""

    def __init__(self, enabled: bool, trace_dir: str) -> None:
        self.dir = trace_dir
        self.state = "idle" if enabled else "done"
        self.anchor_monotonic: Optional[float] = None
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if self.state != "idle":
            return
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("perf_anchor"):
            self.anchor_monotonic = time.monotonic()
        self.t_start = time.monotonic()
        self.state = "tracing"

    def stop(self) -> None:
        if self.state != "tracing":
            return
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()
        self.state = "done"


class Checks:
    """The numbers compared, each printed beside its limit."""

    def __init__(self) -> None:
        self.rows: List[Dict[str, Any]] = []

    def add(self, name: str, value: float, limit: Optional[float]) -> None:
        ok = limit is not None and value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit, "ok": ok})
        say("check", name=name, value=value, limit=limit, ok=ok)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


class Context:
    """What a traffic module is handed."""

    def __init__(self, **kw: Any) -> None:
        self.__dict__.update(kw)
        self.removals: List[threading.Thread] = []

    def put_tokens(self, tokens):
        import jax

        return jax.device_put(tokens, self.token_sharding)

    def next_tokens(self):
        """One fresh seeded batch: every row of every step differs."""
        import numpy as np

        shape = (self.batch, self.seq_len)
        return self.rng.integers(0, self.vocab, shape).astype(np.int32)

    def app_state(self, tree):
        from tpusnap import PytreeState

        return {"train": PytreeState(tree)}

    def take_kwargs(self) -> Dict[str, Any]:
        """The control ``store_bf16`` switches on the program's own
        lower-precision path: every float32 leaf is stored as bfloat16."""
        if self.control == "store_bf16":
            import jax.numpy as jnp
            from tpusnap.transforms import cast_on_save

            globs = ("train/params/*", "train/opt/mu/*", "train/opt/nu/*")
            return {"_custom_array_prepare_func": cast_on_save(
                {g: jnp.bfloat16 for g in globs})}
        return {}

    def remove_later(self, path: str) -> None:
        """Off the loop's thread, as a trainer's retention does it."""
        t = threading.Thread(target=shutil.rmtree, args=(path, True), daemon=True)
        t.start()
        self.removals.append(t)

    def fingerprints(self, tree):
        """Two 32-bit sums over every leaf: of each element's bits, mixed
        (murmur3's finalizer, one to one on 32 bits), and of the same
        weighted by the element's place with an odd weight, so that one
        altered element always shows and elements that changed places all
        but always: what a restored state is compared with where the
        state that was saved is gone, because a donating step has deleted
        it. Eight bytes a leaf on the device, where a copy would be the
        state again. One program a tree shape: warmed up in set-up
        wherever the window calls it."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        def leaf(x):
            h = _bits(x).astype(jnp.uint32)
            h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
            h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
            h = h ^ (h >> 16)
            place, stride = jnp.zeros(x.shape, jnp.uint32), 1
            for axis in reversed(range(x.ndim)):
                iota = lax.broadcasted_iota(jnp.uint32, x.shape, axis)
                place = place + iota * jnp.uint32(stride % 2**32)
                stride *= x.shape[axis]
            return jnp.stack([jnp.sum(h, dtype=jnp.uint32),
                              jnp.sum(h * (2 * place + 1), dtype=jnp.uint32)])

        if "_fingerprints" not in self.__dict__:
            self._fingerprints = jax.jit(lambda t: jax.tree.map(leaf, t))
        return self._fingerprints(tree)

    def zeroed_targets(self):
        """A zeroed tree of the state's shapes and shardings (one program,
        built once: the window may call this)."""
        import jax
        import jax.numpy as jnp

        if "_zeros" not in self.__dict__:
            self._zeros = jax.jit(
                lambda: jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), self.state_shapes
                ),
                out_shardings=self.state_shardings,
            )
        return self._zeros()


def _memory_stats(devices) -> Optional[List[Dict[str, int]]]:
    stats = [d.memory_stats() for d in devices]
    return None if any(s is None for s in stats) else stats


class MemoryMarks:
    """The allocator's readings on every chip at the end of each phase. The
    peak is a high-water mark of the whole process and cannot be reset, so
    the phase in which each chip's peak was set is worked out from these:
    a peak set by the plain reference would not be the program's."""

    def __init__(self, devices) -> None:
        self.devices = devices
        self.marks: List[Any] = []

    def mark(self, phase: str) -> Optional[List[Dict[str, int]]]:
        stats = _memory_stats(self.devices)
        if stats:
            peaks = [s["peak_bytes_in_use"] for s in stats]
            self.marks.append((phase, peaks))
            say("memory", phase=phase, peak_bytes_in_use=peaks,
                bytes_in_use=[s["bytes_in_use"] for s in stats],
                bytes_limit=stats[0]["bytes_limit"])
        return stats

    def peak_set_in(self) -> List[str]:
        """For each chip, the first phase at whose end its peak stood
        where it stands now."""
        last = self.marks[-1][1]
        return [next(phase for phase, peaks in self.marks if peaks[i] == last[i])
                for i in range(len(last))]


def _leaf_paths(tree) -> List[str]:
    import jax

    return [
        "/".join(str(getattr(p, "key", p)) for p in path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def _bits(x):
    """A floating array's bits as unsigned integers of its width; any other
    array as it is."""
    import jax.numpy as jnp
    from jax import lax

    if jnp.issubdtype(x.dtype, jnp.floating):
        width = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}
        return lax.bitcast_convert_type(x, width[x.dtype.itemsize])
    return x


def _laid_out_as(got, dtype, shape, sharding) -> bool:
    import jax

    return (isinstance(got, jax.Array) and got.dtype == dtype and got.shape == shape
            and got.sharding.is_equivalent_to(sharding, len(shape)))


def count_mismatches(want_tree, got_tree) -> int:
    """Elements of ``got_tree`` whose bits differ from ``want_tree``'s, plus
    one for every leaf whose type, shape or sharding differs. Compared on
    the device, leaf by leaf, so that no second copy of the state is made."""
    import jax
    import jax.numpy as jnp

    differ = jax.jit(lambda a, b: jnp.sum(_bits(a) != _bits(b), dtype=jnp.int32))
    bad = 0
    want_leaves, got_leaves = jax.tree.leaves(want_tree), jax.tree.leaves(got_tree)
    if len(want_leaves) != len(got_leaves):
        return max(len(want_leaves), len(got_leaves))
    for want, got in zip(want_leaves, got_leaves):
        if not _laid_out_as(got, want.dtype, want.shape, want.sharding):
            bad += 1
            continue
        bad += int(differ(want, got))
    return bad


def count_fingerprint_mismatches(ctx: Context, want_prints, got_tree) -> int:
    """``count_mismatches`` where the state to compare with is gone (a
    donating step has deleted it) and ``Context.fingerprints`` of it, taken
    while it lived, stand in its place: the leaves of ``got_tree`` whose
    type, shape or sharding is not the state's, or whose fingerprints
    differ from ``want_prints``'s."""
    import jax
    import numpy as np

    got_leaves = jax.tree.leaves(got_tree)
    shapes, shardings = jax.tree.leaves(ctx.state_shapes), jax.tree.leaves(ctx.state_shardings)
    if len(got_leaves) != len(shapes):
        return max(len(got_leaves), len(shapes))
    bad = [not _laid_out_as(got, shape.dtype, shape.shape, sharding)
           for got, shape, sharding in zip(got_leaves, shapes, shardings)]
    if not any(bad):  # fingerprints of a tree of another layout would say nothing more
        want = jax.tree.leaves(jax.device_get(want_prints))
        got = jax.tree.leaves(jax.device_get(ctx.fingerprints(got_tree)))
        bad = [not np.array_equal(w, g) for w, g in zip(want, got)]
    return sum(bad)


def seed_key(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def reference_first_steps(config, key, tokens, devices, quant=None) -> Dict[str, Any]:
    """The plain reference's first steps, run before the program's state is
    made and freed before it, so the memory peak stays the program's."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    arch = config_module(config, "reference")
    # Where one chip cannot hold it, every matrix is split along its last
    # axis over all the chips. A layout only; no value changes.
    mesh = Mesh(np.asarray(devices), ("all",))
    n = len(devices)

    def place(tree):
        def pin(x):
            last = "all" if x.ndim >= 2 and x.shape[-1] % n == 0 else None
            spec = P(*([None] * (x.ndim - 1) + [last])) if x.ndim else P()
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

        return jax.tree.map(pin, tree)

    rep = NamedSharding(mesh, P())
    with jax.default_matmul_precision("highest"):
        out = first_steps_module().first_steps(
            arch,
            jax.device_put(key, rep),
            [jax.device_put(t, rep) for t in tokens],
            arch.sizes(config), quant, place,
        )
        out = jax.device_get(out)
    return {
        "losses": [float(x) for x in out["losses"]],
        "grad_norms": {k: float(v) for k, v in out["grad_norms"].items()},
        "delta_norms": {k: float(v) for k, v in out["delta_norms"].items()},
        "first_mu": out["first_mu"],
    }


def program_first_steps(ctx: Context, tokens: Sequence[Any]) -> Dict[str, Any]:
    """Drives the window's own compiled step and state through the first
    steps, on the window's own feed, and reads the same numbers off it."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(
        lambda tree: jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)
    )
    delta_norms = jax.jit(
        lambda a, b: jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b)
    )
    # A step that donates deletes the state it is handed: what is read
    # after a later step (the starting parameters, the first step's first
    # moment) is then a copy of the harness's own, 4 bytes a parameter
    # each, dropped with the comparison. Where nothing is donated the
    # trees themselves stay, as they always did.
    keep = (lambda tree: tree) if not ctx.donates else jax.jit(
        lambda tree: jax.tree.map(jnp.copy, tree))
    params0 = keep(ctx.state["params"])
    paths = _leaf_paths(params0)
    losses, grad_norms, first_mu = [], None, None
    for batch in tokens:
        ctx.state, loss = ctx.train_step(ctx.state, ctx.put_tokens(batch))
        losses.append(float(loss))
        if grad_norms is None:
            # Adam's first moment after one step is (1 - b1) * g: the
            # gradient as the optimizer got it. Kept (see above) until the
            # comparison drops it.
            b1 = first_steps_module().ADAM["b1"]
            mu = keep(ctx.state["opt"]["mu"])
            first_mu = dict(zip(paths, jax.tree.leaves(mu)))
            got = jax.device_get(norms(mu))
            grad_norms = {
                p: float(v) / (1.0 - b1) for p, v in zip(paths, jax.tree.leaves(got))
            }
    got = jax.device_get(delta_norms(ctx.state["params"], params0))
    return {
        "losses": losses,
        "grad_norms": grad_norms,
        "delta_norms": {p: float(v) for p, v in zip(paths, jax.tree.leaves(got))},
        "first_mu": first_mu,
    }


def first_step_gaps(got, want) -> Dict[str, float]:
    """The four numbers compared of the first steps: the widest relative
    loss gap; by the worst leaf the gap of the first gradient's norm and of
    the norm of the parameters' change; and, first order in the arithmetic's
    precision, by the worst leaf the norm of the first gradient's difference
    (read off Adam's first moment after one step, which is the gradient
    times ``1 - b1`` on both sides)."""
    shared = first_steps_module()
    return {
        "loss_gap": max(abs(g - w) / abs(w) for g, w in zip(got["losses"], want["losses"])),
        "grad_norm_gap": shared.worst_leaf_gap(got["grad_norms"], want["grad_norms"]),
        "delta_norm_gap": shared.worst_leaf_gap(got["delta_norms"], want["delta_norms"]),
        "grad_diff": shared.worst_leaf_diff(got["first_mu"], want["first_mu"]),
    }


def compare_first_steps(checks: Checks, got, want, limits: Dict[str, Any]) -> None:
    for name, value in first_step_gaps(got, want).items():
        checks.add(name, value, limits.get(name))


def _fs_info(path: str) -> Dict[str, Any]:
    best = ("", "unknown")
    try:
        with open("/proc/mounts") as f:
            for line in f:
                _, mount, fstype = line.split()[:3]
                if os.path.realpath(path).startswith(mount) and len(mount) > len(best[0]):
                    best = (mount, fstype)
    except OSError:
        pass
    usage = shutil.disk_usage(path)
    return {"dir": path, "mount": best[0], "fstype": best[1], "free_bytes": usage.free}


def find_devices(chips: int, rehearsal: bool):
    """The cell's chips, or None: a measurement path that finds no chip
    fails; it never falls back to another backend."""
    import jax

    found = jax.devices()
    platform = found[0].platform
    if (platform != "tpu" and not rehearsal) or len(found) < chips:
        print(
            f"perf: JAX found {len(found)} {platform!r} device(s) "
            f"({found[0].device_kind}); this cell needs {chips} TPU chip(s). "
            "No result is printed on another backend.",
            file=sys.stderr,
        )
        return None
    return found[:chips]


def build_program(config, devices, seed: int, **extra: Any) -> Context:
    """The system under test at the configuration's sizes, as the program
    that the configuration names builds it from the seed (mesh, state on
    the device, the compiled train step, the shardings, whether the step
    donates its state), and what is the harness's own: the state's shapes
    and bytes, and the seeded token feed."""
    import jax
    import numpy as np

    built = config_module(config, "program").build(config, devices, seed_key(seed))
    # A program whose step deletes the state it is handed says so; the
    # harness and the traffic kind then read no state after passing it on.
    built["donates"] = bool(built.get("donates", False))
    state = built["state"]
    return Context(
        config=config, devices=devices, **built,
        state_shapes=jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state),
        state_bytes=sum(x.nbytes for x in jax.tree.leaves(state)),
        batch=int(config["assumed"]["batch"]), seq_len=int(config["assumed"]["seq_len"]),
        vocab=int(config["vocab_size"]), rng=np.random.default_rng(seed), say=say, **extra,
    )


def first_tokens(config, seed: int):
    """The batches of the first steps: the head of the window's own feed."""
    import numpy as np

    feed = Context(
        batch=int(config["assumed"]["batch"]), seq_len=int(config["assumed"]["seq_len"]),
        vocab=int(config["vocab_size"]), rng=np.random.default_rng(seed),
    )
    return [feed.next_tokens() for _ in range(first_steps_module().N_STEPS)]


def run_cell(manifest, cell, args, t_process_start: float) -> int:
    import jax

    from tpusnap import compile_cache

    cache_dir = compile_cache.enable()
    devices = find_devices(int(cell["chips"]), args.rehearsal)
    if devices is None:
        return 2
    work_dir = tempfile.mkdtemp(prefix="tpusnap_perf_")
    os.environ.setdefault("TPUSNAP_TELEMETRY_DIR", os.path.join(work_dir, "telemetry"))
    trap = WarningTrap()
    logging.getLogger("tpusnap").addHandler(trap)
    try:
        say("compile_cache", dir=cache_dir,
            entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
        return _run(manifest, cell, args, t_process_start, devices, work_dir, trap)
    finally:
        logging.getLogger("tpusnap").removeHandler(trap)
        shutil.rmtree(work_dir, ignore_errors=True)


def _run(manifest, cell, args, t_process_start, devices, work_dir, trap) -> int:
    import jax

    from tpusnap import metrics_sink, telemetry

    config = read_json("configs", f"{cell['config']}.json")
    traffic = read_json("traffic", f"{cell['traffic']}.json")
    if args.rehearsal:
        config = read_json("configs", f"{config['rehearsal_config']}.json")
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    # Knobs of the program that the mix fixes (the guarantee it is run
    # under): set before the program reads them.
    os.environ.update({k: str(v) for k, v in traffic.get("env", {}).items()})
    kind = load_module("traffic", traffic["kind"])
    # Both are found before anything is built; the preset that a rehearsal
    # puts in the configuration's place states its own.
    arch = config_module(config, "reference")
    config_module(config, "program")

    events: collections.Counter = collections.Counter()
    phase = {"name": "setup"}
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: events.update([(phase["name"], event)])
    )
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    say("device", rehearsal=args.rehearsal, jax=jax.__version__, **device)
    say("work_dir", **_fs_info(work_dir))
    checks = Checks()

    # The plain reference first, before the program's state exists. Its
    # time and the comparison's are no part of set-up: both are taken out
    # of setup_s below.
    tokens = first_tokens(config, args.seed)
    t_ref = time.monotonic()
    want = reference_first_steps(config, seed_key(args.seed), tokens, devices)
    check_s = time.monotonic() - t_ref
    say("reference", seconds=check_s, losses=want["losses"])
    memory = MemoryMarks(devices)
    memory.mark("reference")

    ctx = build_program(
        config, devices, args.seed, params=traffic, control=args.control,
        work_dir=work_dir, checks=checks, log=None,
        tracer=Tracer(bool(args.trace), os.path.join(work_dir, "trace")),
    )
    memory.mark("state_built")
    if ctx.donates and not getattr(kind, "SERVES_A_DONATING_STEP", False):
        print(
            f"perf: the program {config['program']!r} donates its state to the step, and "
            f"the traffic kind {traffic['kind']!r} reads a state after the step has had it "
            "(it does not set SERVES_A_DONATING_STEP). No result is printed.",
            file=sys.stderr,
        )
        return 2
    # The program's feed goes on where the first steps' batches end.
    for _ in tokens:
        ctx.next_tokens()
    # A state that is not the size the configuration's reference works out
    # from its sizes (parameters, the optimizer's moments, counters) is refused.
    checks.add("state_bytes_off", abs(ctx.state_bytes - arch.state_bytes(config)), 0)

    # Warm-up, which is also the check of the train step: the window's own
    # compiled step and state go through the first steps.
    got = program_first_steps(ctx, tokens)
    say("first_steps", losses=got["losses"])
    t_compare = time.monotonic()
    compare_first_steps(checks, got, want, config.get("limits", {}))
    del got, want  # both sides' first moments
    check_s += time.monotonic() - t_compare
    say("comparison", seconds=time.monotonic() - t_compare)
    memory.mark("first_steps")
    kind.setup(ctx)
    jax.block_until_ready(ctx.state)
    memory.mark("warm_up")
    setup_s = time.monotonic() - t_process_start - check_s
    say("setup", setup_s=setup_s, state_bytes=ctx.state_bytes,
        events={f"{p}:{e}": n for (p, e), n in sorted(events.items())})

    phase["name"] = "window"
    if args.trace:
        ctx.log = SpanLog()
        with metrics_sink(ctx.log):
            result = kind.run(ctx, float(args.seconds))
    else:
        result = kind.run(ctx, float(args.seconds))
    ctx.tracer.stop()
    phase["name"] = "after"
    in_window = {e: n for (p, e), n in events.items() if p == "window" and e in COMPILE_EVENTS}
    say("window", seconds=args.seconds, compile_events_in_window=in_window,
        **{k: v for k, v in result.items() if k not in ("series", "ops")})
    checks.add("compile_events_in_window", sum(in_window.values()), 0)
    stats = memory.mark("window")
    device["memory_peak_bytes"] = max(s["peak_bytes_in_use"] for s in stats) if stats else 0
    if stats:
        set_in = memory.peak_set_in()
        say("memory_peak", set_in=set_in)
        if "reference" in set_in:
            print("perf: the plain reference, not the program, set a chip's memory peak",
                  file=sys.stderr)

    kind.check(ctx, result)
    memory.mark("check")  # for the record: the peak reported is the window's mark
    fallbacks = telemetry.counter_value("batcher.device_pack_fallbacks")
    unexpected = [m for m in trap.messages if not (fallbacks and "fell back" in m)]
    say("pack_fallbacks", counted=fallbacks)
    checks.add("tpusnap_warnings", len(unexpected), 0)
    for t in ctx.removals:
        t.join()

    obs = {
        "spans": ctx.log.spans if ctx.log else [],
        "counters": ctx.log.counters if ctx.log else [],
        "ops": result.get("ops", []),
        "series": result.get("series", {}),
        "window": result.get("end_to_end", {}),
        "state_bytes": ctx.state_bytes,
        "memory": stats,
        "trace": None,
    }
    breakdown = None
    if args.trace:
        obs["trace"] = load_module("reducers", "_trace").summarise(
            ctx.tracer, obs["spans"])
        if obs["trace"]:
            device["busy_s"] = obs["trace"]["busy_s"]
            device["window_s"] = obs["trace"]["window_s"]
            breakdown = obs["trace"]["breakdown"]
            say("trace", **{k: v for k, v in obs["trace"].items() if k != "breakdown"})
        elif not args.rehearsal:
            print("perf: the trace holds no device operation", file=sys.stderr)
            return 3

    metrics: Dict[str, Any] = {}
    for m in manifest["per_layer"] if args.trace else manifest["end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        is_count = False
        if m["name"] == "setup_s":
            value = setup_s
        elif args.trace:
            spec = layer_metric_spec(m["name"])
            value = load_module("reducers", spec["reducer"]).reduce(obs, **spec.get("args", {}))
            is_count = bool(spec.get("count"))
        else:
            value = result["end_to_end"].get(m["name"])
        if value is None:
            continue  # a reader that found nothing to read
        if args.rehearsal and not is_count:
            value = None  # a time from the CPU is never printed under a metric's name
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    quiet = result.get("series", {}).get("quiet_step_ms")
    if quiet and not args.rehearsal:
        peaks = read_json("peaks.json")["devices"]
        if device["kind"] not in peaks:
            print(f"perf: no peaks for device kind {device['kind']!r}", file=sys.stderr)
            return 3
        flops = arch.train_flops_per_token(config, ctx.seq_len) * ctx.batch * ctx.seq_len
        share = flops / (statistics.median(quiet) / 1e3) / (
            peaks[device["kind"]]["bf16_flops_per_s"] * len(devices))
        say("model_flop_share_of_quiet_step", share=share, flops_per_step=flops)

    failed = int(result.get("failed", 0))
    checks.add("failed", failed, 0)
    line: Dict[str, Any] = {
        "correct": checks.correct,
        "attempted": int(result.get("attempted", 0)),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    if args.control:
        say("control", name=args.control, correct=line["correct"])
    # Each number compared beside its limit: the last lines of standard
    # error, and the last key of the result's line.
    line["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]} for r in checks.rows}
    for r in checks.rows:
        print(f"perf compared: {r['name']} {r['value']} limit {r['limit']}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
