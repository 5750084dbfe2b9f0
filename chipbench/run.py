#!/usr/bin/env python3
"""chipbench — the on-chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Finds everything by name: the cell in ``BENCHMARK.json``, its
configuration file, ``chipbench/traffic/<traffic>.json``,
``chipbench/runners/<runner>.py``, ``chipbench/reference/<config>.py``
and, in a traced run, ``chipbench/layer_metrics/<metric>.py``.  The last
line of standard output is the result object; everything else comes
before it.  Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result: there is no CPU fallback.
"""
import time
T_PROCESS = time.perf_counter()

import argparse                                             # noqa: E402
import importlib                                            # noqa: E402
import importlib.util                                       # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def layer_metrics(env, facts, reduced, device_kind):
    """Call the reader of each per-layer metric this cell reports; a
    reader that finds nothing to read returns None and is left out."""
    from chipbench import flops, peaks
    ctx = {"cfg": env.cfg, "traffic": env.traffic, "chips": env.chips,
           "facts": facts, "trace": reduced, "device_kind": device_kind,
           "peaks": peaks, "flops": flops}
    out = {}
    for entry in env.bench["per_layer"]:
        if env.cell["name"] not in entry.get("workloads",
                                             [env.cell["name"]]):
            continue
        path = os.path.join(env.root, "chipbench", "layer_metrics",
                            entry["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "chipbench_metric_" + entry["name"].replace(".", "_"), path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
        value = reader.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def memory_peak(stats):
    """Peak HBM held on one chip.  On this backend ``peak_bytes_in_use``
    counts live buffers only; a running program's temporaries are a
    separate reservation, ``peak_bytes_reserved`` (8,414,478,336 B for the
    ResNet-50 step at 256 on the chip, where XLA's ``memory_analysis()`` of
    the same step says 8,427,210,752 B of temporaries; PERF.md section 5).
    The two regions are disjoint, so the peak is their sum (an upper bound
    by at most the gap between the two peaks' moments)."""
    return stats.get("peak_bytes_in_use", 0) + \
        stats.get("peak_bytes_reserved", 0)


def execute(env):
    """Run the cell; returns the result object.  The device gate is in
    main(): this is what a rehearsal at toy size calls."""
    import jax
    from chipbench import harness, trace as trace_mod
    env.devices = jax.devices()[:env.chips]
    env.compiles = harness.CompileCount()
    runner = importlib.import_module(
        "chipbench.runners." + env.traffic["runner"])
    res = runner.run(env)
    env.tracer.stop()
    dev0 = env.devices[0]
    stats = [d.memory_stats() or {} for d in env.devices]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(env.devices),
              "memory_peak_bytes": max(memory_peak(s) for s in stats)}
    setup_s = res["t_open"] - env.t_process
    env.say("setup", {"setup_s": setup_s, "phases": env.phases,
                      "compiles_before_window": res["compiles_before"],
                      "compiles_in_window": res["compiles_in_window"]})
    env.say("memory", stats[0])
    result = {"correct": bool(res["correct"]),
              "attempted": int(res["attempted"]),
              "failed": int(res["failed"]), "device": device}
    if not env.traced:
        metrics = dict(res["end_to_end"], setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in env.bench["end_to_end"]}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in metrics.items()}
    else:
        reduced = trace_mod.reduce_rows(env.tracer.rows)
        result["metrics"] = layer_metrics(env, res["facts"], reduced,
                                          dev0.device_kind)
        if reduced is not None:
            device["busy_s"] = reduced["busy_s_mean"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            env.say("trace", {k: reduced[k] for k in (
                "window_s", "busy_s_first", "busy_s_mean", "devices",
                "collective_s_first", "span_counts")})
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()

    from chipbench import harness
    env = harness.Env(ROOT, args.workload, args.seed, args.seconds,
                      args.trace, T_PROCESS)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < env.chips:
        print("chipbench: %s needs %d TPU chip(s); JAX found %d device(s) "
              "of platform %r. Nothing was run."
              % (args.workload, env.chips, len(devs), devs[0].platform),
              file=sys.stderr)
        sys.exit(2)
    # every program goes to the persistent cache, however small or quick to
    # compile, so that only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    result = execute(env)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
