"""Runner ``serve_closed``: the serving tier in process, under a closed
loop of clients that each wait for their reply (front-end servers do).
Checkpoint -> ``serving.load`` with the default bucket ladder and batcher
timeout -> N client threads calling ``serving.predict`` with no think time,
rows per request from the traffic file's mix.  Closed loop copied from
``tools/serve_bench.py`` (``closed_loop`` / ``_Driver.predict``): a shed
request sleeps out its retry hint and tries again, and that wait is part
of its latency.
"""
import os
import re
import shutil
import statistics
import tempfile
import threading
import time

import jax
import mxnet_tpu as mx
import mxnet_tpu.serving as serving
import numpy as np
from mxnet_tpu.serving.batcher import Overloaded

from chipbench import build, check, correct, traffic

MODEL = "chipbench"
_RETRY_IN = re.compile(r"retry in ([0-9.]+)s")


def checkpoint(env, sym, workdir):
    """Seeded weights, BatchNorm running statistics set from the float32
    reference's training-mode pass over one seeded batch (at the init
    statistics mean 0 / var 1 the logits of a 50-layer net saturate and
    every comparison is ill-conditioned).  Returns (prefix, params, aux,
    rows): the reference's inputs and the pool of request rows."""
    cfg, tr = env.cfg, env.traffic
    mx.random.seed(build.fold_seed(env.seed))
    mod = build.bind_module(mx, sym, env.contexts(mx), tr["stats_batch"],
                            cfg["image"], for_training=False)
    mod.init_params(initializer=mx.initializer.Xavier())
    arg_nd, aux_nd = mod.get_params()
    params, aux = build.host_params(mod)
    xs, ys = traffic.image_pool(env.seed, 1, tr["pool_rows"], cfg["image"],
                                cfg["classes"])
    n = tr["stats_batch"]
    with jax.default_matmul_precision("highest"):
        _, _, stats = check.forward_fn(cfg, "float32", True)(
            params, aux, xs[0][:n], ys[0][:n])
    aux = {k: np.asarray(v, np.float32) for k, v in stats.items()}
    aux_nd = {k: mx.nd.array(aux[k], dtype=v.dtype)
              for k, v in aux_nd.items()}
    prefix = os.path.join(workdir, MODEL)
    mx.model.save_checkpoint(prefix, 1, sym, dict(arg_nd), aux_nd)
    return prefix, params, aux, np.asarray(xs[0])


def predict(x, deadline_s):
    """One predict with shed-retry.  Returns (reply, sheds absorbed)."""
    sheds = 0
    t_end = time.perf_counter() + deadline_s
    while True:
        try:
            return serving.predict(MODEL, {"data": x},
                                   timeout=deadline_s)[0], sheds
        except Overloaded as exc:
            m = _RETRY_IN.search(str(exc))
            hint = min(max(float(m.group(1)), 0.01), 1.0) if m else 0.05
            if time.perf_counter() + hint > t_end:
                raise
            sheds += 1
            time.sleep(hint)


class Client(threading.Thread):
    def __init__(self, idx, env, rows, stop):
        super().__init__(daemon=True, name="chipbench-client-%d" % idx)
        tr = env.traffic
        self.schedule = traffic.rows_schedule(env.seed, idx, tr["rows_mix"],
                                              tr["schedule_block"])
        self.rows, self.stop_flag = rows, stop
        self.offset = (idx * 7) % len(rows)
        self.deadline_s = tr["request_deadline_s"]
        self.classes = env.cfg["classes"]
        self.done = []          # (t_submit, t_done, rows)
        self.errors, self.sheds, self.bad = [], 0, 0

    def run(self):
        i = 0
        while not self.stop_flag.is_set():
            n = self.schedule[i % len(self.schedule)]
            i += 1
            if self.offset + n > len(self.rows):
                self.offset = 0
            x = self.rows[self.offset:self.offset + n]
            self.offset += n
            t0 = time.perf_counter()
            try:
                reply, sheds = predict(x, self.deadline_s)
            except Exception as exc:    # noqa: BLE001 — counted as failed
                self.errors.append((time.perf_counter(), repr(exc)[:200]))
                continue
            t1 = time.perf_counter()
            self.sheds += sheds
            self.done.append((t0, t1, n))
            if reply.shape != (n, self.classes) or \
                    not np.isfinite(reply).all() or \
                    np.abs(reply.sum(axis=1) - 1.0).max() > 1e-2:
                self.bad += 1


def counters(slot):
    snap = slot.metrics.snapshot()
    wait = snap["queue_wait_us"]
    return {"rows": snap["rows"], "padded_rows": snap["padded_rows"],
            "batches": snap["batches"], "queue_wait_n": wait["count"],
            "queue_wait_sum_us": wait["count"] * wait["mean"]}


def run(env):
    cfg = env.cfg
    env.phases.append(("import", time.perf_counter() - env.t_process))
    workdir = tempfile.mkdtemp(prefix="chipbench-ckpt-")
    try:
        with env.phase("build_init_checkpoint"):
            sym = build.train_symbol(build.zoo_net(cfg), cfg["dtype"])
            prefix, params, aux, rows = checkpoint(env, sym, workdir)
        with env.phase("load"):
            slot = serving.load(
                MODEL, prefix=prefix, epoch=1, ctx=env.contexts(mx),
                input_shapes={"data": (1, 3, cfg["image"], cfg["image"])})
        return serve(env, slot, params, aux, rows)
    finally:
        if MODEL in serving.get_registry().names():
            serving.unload(MODEL)
        shutil.rmtree(workdir, ignore_errors=True)


def serve(env, slot, params, aux, rows):
    cfg, tr = env.cfg, env.traffic
    # one request of each row count against the reference's inference pass
    with env.phase("check"):
        sizes = sorted(int(r) for r in tr["rows_mix"])
        total = sum(sizes)
        labels = np.zeros((total,), np.float32)
        with jax.default_matmul_precision("highest"):
            _, want, _ = check.forward_fn(cfg, "float32", False)(
                params, aux, rows[:total], labels)
            _, plain, _ = check.forward_fn(cfg, cfg["dtype"], False)(
                params, aux, rows[:total], labels)
        want, plain = np.asarray(want), np.asarray(plain)
        judged, at = [], 0
        for n in sizes:
            got = serving.predict(MODEL, {"data": rows[at:at + n]})[0]
            judged.append(correct.judge("probs@%drows" % n, got,
                                        want[at:at + n], plain[at:at + n]))
            at += n
        ok, report = correct.summarise(judged, [r["name"] for r in judged])
        env.say("deviations", {r["name"]: [r["dev_sys"], r["dev_plain"],
                                           r["verdict"]] for r in judged})
        env.say("check", report)
    del params, aux, want, plain

    slot.batcher._run_batch = env.spans.wrap("batcher",
                                             slot.batcher._run_batch)
    stop = threading.Event()
    clients = [Client(i, env, rows, stop) for i in range(tr["clients"])]
    t_warm = time.perf_counter()
    for c in clients:
        c.start()
    time.sleep(tr["warmup_s"])
    env.phases.append(("warmup", time.perf_counter() - t_warm))
    before = counters(slot)
    compiles_at_open = env.compiles.n
    t_open = time.perf_counter()
    t_close = t_open + env.seconds
    while True:
        now = time.perf_counter()
        if now >= t_close:
            break
        env.tracer.tick(now, t_open)
        time.sleep(min(0.05, t_close - now))
    t_close = time.perf_counter()
    after = counters(slot)
    in_window = env.compiles.n - compiles_at_open
    env.tracer.stop()
    stop.set()
    for c in clients:
        c.join(timeout=tr["request_deadline_s"] + 5)
    alive = [c.name for c in clients if c.is_alive()]

    done = [d for c in clients for d in c.done if t_open <= d[1] <= t_close]
    errors = [e for c in clients for e in c.errors
              if t_open <= e[0] <= t_close]
    lat_ms = sorted((t1 - t0) * 1e3 for t0, t1, _ in done)
    rows_done = sum(n for _, _, n in done)
    seconds = t_close - t_open
    bad = sum(c.bad for c in clients)
    slot_delta = {k: after[k] - before[k] for k in after}
    held = {"reference_rule": ok, "zero_compiles_in_window": in_window == 0,
            "replies_well_formed": bad == 0, "no_errors": not errors,
            "clients_ended": not alive, "requests": len(done) > 0}
    p95 = statistics.quantiles(lat_ms, n=20)[-1] if len(lat_ms) > 20 \
        else float("nan")
    env.say("window", {
        "held": held, "seconds": seconds, "requests": len(done),
        "rows": rows_done, "sheds_absorbed": sum(c.sheds for c in clients),
        "errors": [e[1] for e in errors][:5],
        "latency_ms": {"samples": len(lat_ms), "p50": statistics.median(
            lat_ms) if lat_ms else None, "p95": p95,
            "max": lat_ms[-1] if lat_ms else None},
        "slot": slot_delta})
    return {
        "correct": all(held.values()),
        "attempted": len(done) + len(errors), "failed": len(errors),
        "t_open": t_open, "compiles_before": compiles_at_open,
        "compiles_in_window": in_window,
        "end_to_end": {"serve_items_s": rows_done / seconds,
                       "serve_p95_ms": p95},
        "facts": {"slot": slot_delta},
    }
