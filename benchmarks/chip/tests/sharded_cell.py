"""The sharded path through the harness on four host devices: a tiny
four-mode CP cell whose traffic names `"method": "pallas_sharded"`, run
through run.run untraced and traced.  test_sharded.py starts it in a process
of its own, since the device count is fixed before JAX first loads:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 benchmarks/chip/tests/sharded_cell.py

A CPU trace holds no device planes, so before the reduction each device
gets kernel and collective events inside the window (`KERNEL_US`,
`COLLECTIVE_US`), and the CPU device kind the v5e's peaks.  Prints one JSON
object: both results, the slots and nonzeros of the workspace's stacks, the
reduction, and the one-chip roofline bound.
"""
import json
import sys

import tiny

CELL, CHIPS = "tiny4.cp.sharded", 4
KERNEL_US = (40.0, 30.0, 20.0, 10.0)
COLLECTIVE_US = 5.0
KERNEL_HLO = 'custom-call(), custom_call_target="tpu_custom_call"'


def main() -> None:
    import jax

    import costs.cp
    import trace_reduce
    from trace_reduce import Event

    assert len(jax.devices()) == CHIPS, jax.devices()
    run = tiny.run
    seen = {}
    build, load_json, reduce = run.load_callable, run.load_json, trace_reduce.reduce

    def builder(spec):
        make = build(spec)
        return lambda *a, **k: seen.setdefault("ws", make(*a, **k))

    def json_with_cpu_peaks(path):
        data = load_json(path)
        if path.name == "peaks.json":
            data["cpu"] = data["TPU v5 lite"]
        return data

    def reduce_with_device_events(events, **kw):
        w = next(e for e in events if e.name == trace_reduce.WINDOW)
        for d, us in enumerate(KERNEL_US):
            plane = f"/device:TPU:{d}"
            events.append(Event(plane, trace_reduce.OPS_LINE, "mttkrp_pallas_call.1",
                                w.start_ns + 1000, us * 1e3, KERNEL_HLO))
            events.append(Event(plane, trace_reduce.OPS_LINE, "all-reduce-start.2",
                                w.start_ns + 1000 + us * 1e3, COLLECTIVE_US * 1e3))
        seen["reduction"] = reduce(events, **kw)
        return seen["reduction"]

    run.load_callable, run.load_json = builder, json_with_cpu_peaks
    trace_reduce.reduce = reduce_with_device_events

    out = {}
    for trace in (False, True):
        seen.clear()
        out["trace" if trace else "plain"] = tiny.run_cell(CELL, trace=trace)
    stacks = seen["ws"].stacks.values()
    config = load_json(tiny.DATA / "configs" / "tiny4.json")
    peak = load_json(run.HERE / "peaks.json")["TPU v5 lite"]
    work = costs.cp.kernel_work(config["shape"], config["nnz"], 4)
    out.update(
        slots=[s.nshards * s.nblocks * s.blk for s in stacks],
        nnz=[sum(s.shard_nnz) for s in stacks],
        nshards=seen["ws"].nshards,
        reduction=vars(seen["reduction"]),
        bound_one_chip_s=sum(max(w["bytes"] / peak["hbm_bytes_per_s"],
                                 w["flops"] / peak["flops_per_s"]) for w in work),
    )
    print(json.dumps(out))


if __name__ == "__main__":
    sys.exit(main())
