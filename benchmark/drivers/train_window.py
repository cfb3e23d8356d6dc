"""The training cells' driver: a Trainer built by ``train.main`` as a user
builds it, driven through ``Trainer.fit`` -> ``Trainer.train_epoch`` on fresh
batches from a ``DeviceLoader``, for a fixed number of seconds.

One Trainer serves the whole run. Set-up gives it weights made here from
``--seed``, takes it through its first three optimizer steps (three calls of
the very call the window makes, one step each, so that each step's loss is
an epoch's mean) and keeps the program's readings of them. The window is one
more such call that ends by itself when the loader stops yielding at the
deadline. After the window, with the peak memory read and the program's
state freed, the plain reference follows the same three steps on the rows
the loader served, and the two sets of readings decide ``correct``.

Why ``fit`` and not ``train_epoch`` alone: a Trainer holds its telemetry
scope only inside ``fit``; a bare ``train_epoch`` after it would run without
the telemetry, the step clock's fence and the AOT dispatch that a user's
training pays for.
"""

import gc
import glob
import os
import shutil
import time

import numpy as np

import harness
import train_reference

CHECK_STEPS = 3
# after the compared steps, one more call long enough to pass everything a
# long epoch does that a one-step epoch does not: the running sums of the
# metrics, the step clock's fence (8th step), the log fetch (11th)
WARM_STEPS = 12
TRACE_LEAD_S = 2.0  # steady steps before the profiler starts


class TokenRows:
    """The cell's input: uniform random token rows made from the seed, in
    the map-style shape ``DeviceLoader`` takes (``get_batch`` preferred)."""

    def __init__(self, rows, seq_len, vocab, seed):
        rng = np.random.default_rng(seed)
        self.tokens = rng.integers(0, vocab, (rows, seq_len), dtype=np.int32)

    def __len__(self):
        return len(self.tokens)

    def __getitem__(self, i):
        return {"tokens": self.tokens[i]}

    def get_batch(self, indices):
        return {"tokens": self.tokens[np.asarray(indices)]}


class WindowLoader:
    """A ``DeviceLoader`` as the Trainer sees it, with three additions: the
    seconds spent inside each ``next()`` are summed, the iteration goes on
    from where the last call stopped (and into the loader's next epoch when
    one runs out), and it stops yielding after ``limit`` batches or at
    ``deadline``. Everything else is the loader's own."""

    def __init__(self, loader):
        self._loader = loader
        self.cursor = 0
        self.served = 0
        self.wait_s = 0.0
        self.limit = None
        self.deadline = None
        self.keep = 0  # host copies of this many served batches, for `correct`
        self.kept = []
        self.on_batch = None  # called between batches (the tracer's switch)

    def __getattr__(self, name):
        return getattr(self._loader, name)

    def __len__(self):
        return len(self._loader)

    def arm(self, limit=None, deadline=None):
        self.limit, self.deadline = limit, deadline
        self.served, self.wait_s = 0, 0.0

    def _stop(self):
        if self.limit is not None and self.served >= self.limit:
            return True
        return self.deadline is not None and time.perf_counter() >= self.deadline

    def __iter__(self):
        import jax

        it = self._loader.iter_from(self.cursor)
        try:
            while not self._stop():
                if self.on_batch is not None:
                    self.on_batch(self)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("next_batch"):
                    batch = next(it, None)
                self.wait_s += time.perf_counter() - t0
                if batch is None:  # the epoch ran out inside the window
                    self._loader.set_epoch(self._loader.sampler.epoch + 1)
                    self.cursor = 0
                    it = self._loader.iter_from(0)
                    continue
                self.cursor += 1
                self.served += 1
                if len(self.kept) < self.keep:
                    self.kept.append(np.asarray(batch["tokens"]))
                yield batch
        finally:
            it.close()


class Tracer:
    """Switches JAX's profiler on for ``seconds`` of the steady window, from
    between two batches on the training thread."""

    def __init__(self, directory, start_at, seconds):
        self.directory, self.start_at, self.seconds = directory, start_at, seconds
        self.started = self.stopped = None
        self.wait_at_start = self.wait_at_stop = None

    def __call__(self, loader):
        import jax

        now = time.perf_counter()
        if self.started is None and now >= self.start_at:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans are the annotations
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.started, self.wait_at_start = time.perf_counter(), loader.wait_s
        elif self.stopped is None and self.started is not None \
                and now >= self.started + self.seconds:
            self.stop(loader)

    def stop(self, loader):
        import jax

        if self.started is not None and self.stopped is None:
            self.stopped, self.wait_at_stop = time.perf_counter(), loader.wait_s
            jax.profiler.stop_trace()

    def trace_file(self):
        found = glob.glob(
            os.path.join(self.directory, "plugins", "profile", "*", "*.xplane.pb")
        )
        return found[0] if found else None


class TrainRun:
    """One Trainer, its loader and the benchmark's own weights and rows."""

    def __init__(self, config, traffic, seed, devices):
        import train  # the program's entry point, at the checkout's root

        self.config, self.traffic, self.devices = config, traffic, devices
        self.chips = len(devices)
        self.global_rows = traffic["rows_per_chip"] * self.chips
        self.seq_len = traffic["seq_len"]
        self.model = harness.load_module("reference", config["reference"])
        adam = traffic["adam"]
        argv = list(config["train_argv"]) + [
            "--seq-len", str(self.seq_len),
            "--batch-size", str(traffic["rows_per_chip"]),
            "--optimizer", "adam", "--lr", repr(adam["lr"]),
            "--seed", str(_program_seed(seed)),
            # the Trainer is built and its state laid out; its steps are
            # taken below, through the loader of the benchmark
            "--epochs", "0", "--num-samples", str(2 * self.global_rows),
            "--checkpoint-dir", "",
        ] + list(traffic["train_argv"])
        harness.say("train.main " + " ".join(argv))
        self.trainer = train.main(argv, devices=devices)
        mesh = self.trainer.partitioner.mesh
        if mesh.size != self.chips:
            raise SystemExit(
                f"benchmark: the Trainer's mesh spans {mesh.size} devices, "
                f"the cell {self.chips}"
            )
        self.names = self.model.program_names(config)
        self._make_params = None
        self.reseed(seed)

    def reseed(self, seed):
        """This seed's rows, loader, weights and masking key; the Trainer and
        its compiled step stay (the calibration reads many seeds in one
        process, a run only one)."""
        import jax

        import distributed_pytorch_example_tpu as dpx

        data_seed, weight_seed, mask_seed = harness.seed_words(seed, 3)
        self.rows = TokenRows(
            self.traffic["dataset_rows"], self.seq_len,
            self.config["vocab_size"], data_seed,
        )
        self.loader = WindowLoader(dpx.data.DeviceLoader(
            self.rows, self.global_rows, mesh=self.trainer.partitioner.mesh,
            shuffle=True, seed=_program_seed(seed),
        ))
        self.weight_key = jax.random.key(weight_seed)
        self._mask_seed = mask_seed
        self.records = []  # the epoch record of every `fit` call
        self.give_weights()

    @property
    def mask_key(self):
        """The key the step's random draws (masked-LM) start from: made
        anew each time, because the copy in the Trainer's state is donated
        to the step."""
        import jax

        return jax.random.key(self._mask_seed)

    # -- the benchmark's weights, in the program's state ------------------

    def reference_params(self):
        """This seed's weights under the reference's own names, made anew
        at each call (``ReferenceSteps.run`` consumes what it is given)."""
        import jax

        return jax.jit(
            lambda key: self.model.init_params(key, self.config)
        )(self.weight_key)

    def give_weights(self):
        """Put this seed's weights, fresh moments, step 0 and the
        benchmark's masking key into the Trainer's state, laid out as the
        Trainer laid its own out, in one jitted call on the device."""
        import jax
        import jax.numpy as jnp

        state, shardings = self.trainer.state, self.trainer.state_shardings
        like = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params
        )
        if jax.tree_util.tree_structure(like) != jax.tree_util.tree_structure(self.names):
            raise SystemExit(
                "benchmark: the program's parameter tree is not the one "
                f"reference/{self.config['reference']}.py::program_names describes"
            )

        def make(key, old):
            flat = self.model.init_params(key, self.config)
            params = jax.tree_util.tree_map(
                lambda name, x: flat[name].reshape(x.shape).astype(x.dtype),
                self.names, old.params,
            )
            return old.replace(
                step=jnp.zeros_like(old.step),
                params=params,
                opt_state=jax.tree_util.tree_map(jnp.zeros_like, old.opt_state),
            )

        if self._make_params is None:
            self._make_params = jax.jit(
                make, out_shardings=shardings, donate_argnums=1
            )
        state = self._make_params(self.weight_key, state)
        rng = jax.device_put(self.mask_key, state.rng.sharding)
        self.trainer.state = state.replace(rng=rng)

    # -- the timed call ---------------------------------------------------

    def fit(self, limit=None, deadline=None):
        """The call the window makes. Returns the epoch's record."""
        import jax

        self.loader.arm(limit=limit, deadline=deadline)
        with jax.profiler.TraceAnnotation("train_epoch"):
            history = self.trainer.fit(self.loader, None, epochs=1)
        self.records.append(history[0])
        return history[0]

    def program_readings(self):
        """The first ``CHECK_STEPS`` steps through the window's own call:
        each step's loss, the first gradient's norm by leaf (from Adam's
        first moment after one step: m1 = (1 - b1) g1) and the change's norm
        by leaf after the last."""
        import jax

        self.loader.keep, self.loader.kept = CHECK_STEPS, []
        norms = jax.jit(train_reference.leaf_norms)
        names = self.names

        def change(params, key):
            # the weights the steps started from, made again here and not
            # kept on the device through the steps; the barrier keeps their
            # making out of the sums, which add up as they would alone
            flat = jax.lax.optimization_barrier(
                self.model.init_params(key, self.config)
            )
            return train_reference.leaf_norms(jax.tree_util.tree_map(
                lambda name, x: x - flat[name].reshape(x.shape), names, params
            ))

        losses, grad_norms = [], None
        for step in range(CHECK_STEPS):
            losses.append(float(self.fit(limit=1)["train_loss"]))
            if step == 0:
                moment = _first_moment(self.trainer.state.opt_state)
                scale = 1.0 - self.traffic["adam"]["b1"]
                grad_norms = self._by_name(norms(moment), 1.0 / scale)
        change_norms = self._by_name(
            jax.jit(change)(self.trainer.state.params, self.weight_key), 1.0
        )
        return {
            "losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms,
        }

    def _by_name(self, tree, scale):
        import jax

        names = jax.tree_util.tree_leaves(self.names)
        values = jax.tree_util.tree_leaves(jax.device_get(tree))
        return {n: float(v) * scale for n, v in zip(names, values)}

    def free(self):
        """Let go of the program's state before the reference runs."""
        self.trainer.state = None
        self.trainer._compiled.clear()
        self.trainer = None
        self.loader = None
        self._make_params = None
        gc.collect()

    def reference_steps(self, dot=train_reference.plain_dot):
        return train_reference.ReferenceSteps(
            self.model, self.config, self.traffic["adam"],
            self.traffic["reference_block_rows"], dot, self.devices,
        )


def _program_seed(seed):
    """``train.py --seed`` and the sampler take 31 bits; there the seed only
    orders the rows (weights and rows are made here, from all of it)."""
    return seed % (2 ** 31 - 1)


def _first_moment(opt_state):
    """Adam's first moment, wherever the optimizer's state keeps it."""
    import jax

    found = [
        node.mu for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "mu")
        ) if hasattr(node, "mu")
    ]
    if len(found) != 1:
        raise SystemExit(
            f"benchmark: expected one Adam state in the optimizer's state, "
            f"found {len(found)}"
        )
    return found[0]


def held_to_zero(config, records):
    """``{name: (value, 0)}`` for the program's own counts that the
    configuration holds to zero (``exact_zero`` in its file: a count only
    the program can make, such as the assignments an expert layer dropped).
    The value is the largest over the epoch records of the run's ``fit``
    calls. A record without the name ends the run: the program has stopped
    reporting what ``correct`` rests on."""
    entries = {}
    for name in config.get("exact_zero", []):
        lacking = [i for i, record in enumerate(records) if name not in record]
        if lacking:
            raise SystemExit(
                f"benchmark: the configuration holds {name!r} to zero, but "
                f"the epoch records {lacking} of {len(records)} lack it"
            )
        values = [float(record[name]) for record in records]
        # a NaN is the worst there is
        entries[name] = (next((v for v in values if v != v), max(values)), 0)
    return entries


def run(cell, config, traffic, bench, args, clock, devices, peak):
    """One run of a training cell; returns (result, compared)."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = harness.CompileLog()
    phases = {"imports": clock.since_start()}
    job = TrainRun(config, traffic, args.seed, devices)
    phases["trainer_built"] = clock.since_start()
    trainer = job.trainer
    program = job.program_readings()
    phases["first_steps"] = clock.since_start()
    job.fit(limit=WARM_STEPS)
    phases["warm"] = clock.since_start()
    taken_before = CHECK_STEPS + WARM_STEPS
    tokens_per_step = job.global_rows * job.seq_len

    # -- the window -------------------------------------------------------
    tracer = None
    trace_dir = os.path.join(harness.BENCH_DIR, ".trace", cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(
            trace_dir, time.perf_counter() + TRACE_LEAD_S,
            min(traffic["trace_seconds"], max(args.seconds - TRACE_LEAD_S - 1, 1)),
        )
        job.loader.on_batch = tracer
    inner = job.loader._loader
    stall_before = (inner.data_stall_ms, inner.stalled_batches, inner.batches_served)
    jax.block_until_ready(trainer.state)
    before, names_before = compiles.snapshot(), len(compiles.names)
    setup_s = clock.since_start()
    opened = time.perf_counter()
    record = job.fit(deadline=opened + args.seconds)
    steps_done = int(jax.device_get(trainer.state.step)) - taken_before
    closed = time.perf_counter()
    if tracer is not None:
        tracer.stop(job.loader)
    after = compiles.snapshot()
    window_s = closed - opened
    steps = job.loader.served
    wait_s = job.loader.wait_s
    bad_steps = int(trainer.recovery["bad_steps"])
    compiled_inside = after["programs"] - before["programs"]
    stall = (
        inner.data_stall_ms - stall_before[0],
        inner.stalled_batches - stall_before[1],
        inner.batches_served - stall_before[2],
    )
    memory_peak = harness.memory_peak_bytes(devices)
    step_memory = _step_memory(trainer)
    served_batches = list(job.loader.kept)
    dataset_tokens = job.rows.tokens
    harness.say(
        f"window {window_s:.3f} s, {steps} steps dispatched, {steps_done} "
        f"taken by the state, last epoch loss {record['train_loss']:.4f}, "
        f"{bad_steps} bad; inside next() {wait_s:.4f} s; DeviceLoader stalled "
        f"{stall[0]:.1f} ms over {stall[1]} of {stall[2]} batches; "
        f"compiles {after}, in the window {compiles.names[names_before:]}; memory_peak_bytes {memory_peak}; "
        f"train step memory_analysis {step_memory}; memory_stats "
        f"{devices[0].memory_stats()}; set-up reached (s) {phases}"
    )

    # -- the reference, once the program's state is gone ------------------
    reference_steps = job.reference_steps()
    mask_key = job.mask_key
    job.free()
    del trainer, record
    t0 = time.perf_counter()
    reference = reference_steps.run(
        job.reference_params(), served_batches, mask_key
    )
    numbers, leaves = train_reference.compare(program, reference)
    harness.say(
        f"reference: {CHECK_STEPS} steps in {time.perf_counter() - t0:.1f} s; "
        f"losses program {program['losses']} reference {reference['losses']}; "
        f"every gap read {numbers}; worst leaves {leaves}"
    )
    limits = harness.load_json("limits", cell["name"] + ".json")
    compared = {k: (v, limits[k]) for k, v in numbers.items() if k in limits}
    compared["served_rows_not_fresh"] = (
        train_reference.rows_missing(served_batches, dataset_tokens), 0
    )
    compared["steps_not_taken"] = (steps - steps_done, 0)
    compared["programs_compiled_in_window"] = (compiled_inside, 0)
    compared.update(held_to_zero(config, job.records))
    correct = all(value <= limit for value, limit in compared.values())

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": bool(correct), "attempted": steps, "failed": bad_steps,
        "metrics": {}, "device": device,
    }
    if not args.trace:
        result["metrics"] = {
            "tokens_per_s_per_chip": {
                "value": steps_done * tokens_per_step / window_s / len(devices),
                "unit": "tokens/s/chip",
            },
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        return result, compared

    import reduce as reducer

    path = tracer.trace_file()
    if path is None or tracer.stopped is None:
        raise SystemExit("benchmark: the traced window left no trace")
    trace = reducer.load(path, len(devices))
    measured = {  # what a per-layer reader is handed
        "cell": cell, "config": config, "traffic": traffic, "peak": peak,
        "chips": len(devices), "tokens_per_step": tokens_per_step,
        "trace": trace,
        "traced_host_s": tracer.stopped - tracer.started,
        "traced_wait_s": tracer.wait_at_stop - tracer.wait_at_start,
    }
    device["busy_s"] = trace.busy_s()
    device["window_s"] = trace.window_s()
    wanted = [
        m for m in bench["per_layer"]
        if cell["name"] in m.get("workloads", [cell["name"]])
    ]
    for metric in wanted:
        reader = harness.load_module("layer_metrics", metric["name"])
        value = reader.read(measured)
        if value is not None:
            result["metrics"][metric["name"]] = {
                "value": value, "unit": metric["unit"],
            }
    result["breakdown"] = trace.breakdown()
    for note in measured.get("notes", []):
        harness.say(note)
    if not args.keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return result, compared


def _step_memory(trainer):
    """The compiled train step's own account of its memory, for the record."""
    for key, exe in trainer._compiled.items():
        if key[0] == "train" and hasattr(exe, "memory_analysis"):
            mem = exe.memory_analysis()
            return {
                "arguments": mem.argument_size_in_bytes,
                "temporaries": mem.temp_size_in_bytes,
                "aliased": mem.alias_size_in_bytes,
                "outputs": mem.output_size_in_bytes,
            }
    return None
