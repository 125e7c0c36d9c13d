"""A hand-made XSpace, as a text proto, for tests that need a trace whose
every number is known. Times are nanoseconds."""


def hlo(name: str, opcode: str, target: str = "") -> str:
    """An operation's event name as the TPU's profiler writes it: the
    whole HLO instruction."""
    tail = f', custom_call_target=\\"{target}\\"' if target else ""
    return (f"%{name} = (f32[8,128]{{1,0:T(8,128)S(1)}}, s32[]{{:T(128)}}) "
            f"{opcode}(f32[8,128]{{1,0:T(8,128)}} %p.1){tail}")


def xspace(planes: dict) -> str:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}},
    or a list of (line name, events) where two lines share a name; an
    event named ``megastep`` gets the ``step_num`` stat 8."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = {}
        body = []
        pairs = lines.items() if isinstance(lines, dict) else lines
        for lid, (lname, events) in enumerate(pairs, 1):
            evs = []
            for name, start, dur in events:
                mid = names.setdefault(name, len(names) + 1)
                stat = (" stats { metadata_id: 1 int64_value: 8 }"
                        if name == "megastep" else "")
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{start * 1000} duration_ps: {dur * 1000}"
                           f"{stat} }}")
            body.append(f'  lines {{ id: {lid} name: "{lname}" '
                        f"timestamp_ns: 0\n" + "\n".join(evs) + "\n  }")
        meta = [f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                f'"{n}" }} }}' for n, i in names.items()]
        meta.append('  stat_metadata { key: 1 value { id: 1 name: '
                    '"step_num" } }')
        out.append(f'planes {{ id: {pid} name: "{pname}"\n'
                   + "\n".join(body + meta) + "\n}")
    return "\n".join(out) + "\n"


def two_chunks(devices: int = 1) -> dict:
    """Two runs of a training step of 1000 ns each with a 200 ns gap, on
    each device: a while loop (container) around a fusion, two Pallas
    kernels (300 + 100 ns), one all-reduce, 100 ns of idle and a custom
    call that is no kernel per run; plus a short
    unrelated module, and host threads of which one carries the
    program's annotation."""
    planes = {}
    for d in range(devices):
        ops, mods = [], [("jit_tiny(1)", 0, 50)]
        ops.append((hlo("copy.1", "copy"), 0, 50))
        for run in range(2):
            t = 1000 + run * 1200
            mods.append(("jit_step(7)", t, 1000))
            ops += [(hlo("while.3", "while"), t, 1000),
                    (hlo("fusion.1", "fusion"), t, 300),
                    (hlo("level_pass.2", "custom-call", "tpu_custom_call"),
                     t + 300, 300),
                    (hlo("table_lookup.9", "custom-call",
                         "tpu_custom_call"), t + 600, 100),
                    (hlo("all-reduce.4", "all-reduce"), t + 700, 100),
                    # 100 ns idle
                    (hlo("custom-call.5", "custom-call", "ConcatBitcast"),
                     t + 900, 100)]
        planes[f"/device:TPU:{d}"] = {
            "XLA Ops": ops, "XLA Modules": mods,
            "Async XLA Ops": [(hlo("copy-start.6", "copy-start"), 1000, 50)],
            "Steps": [("7", 1000, 2200)]}
    # the profiler names every Python thread's line "python3": the
    # program's thread, then the harness's own trace thread
    planes["/host:CPU"] = [
        ("python3", [("megastep", 1001, 50),
                     ("$gbdt.py:1 drain_pending", 2000, 190),
                     ("$api.py:2 device_get", 2010, 150)]),
        ("python3", [("$threading.py:323 wait", 0, 5000),
                     ("$<unknown> acquire", 2005, 140)]),
    ]
    return planes
