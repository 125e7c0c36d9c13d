"""The hand-made trace of synthetic_trace.py with what the chip's profiler
adds to every operation of a device plane: the ``tf_op`` (the
instruction's ``op_name`` and a colon) and ``source`` stats of its
``XEventMetadata``. Times are nanoseconds."""
from synthetic_trace import hlo

STEP = "jit(step)/while/body/closed_call/"
GROW = STEP + "lgbm.grow/jit(grow_tree_fused)/"
# instruction -> (op_name, source); an instruction that is not here is
# one the compiler made itself: it carries no name
NAMES = {
    "while.3": ("jit(step)/while", "gbdt.py:4600"),
    "level_pass.2": (GROW + "level/cond/branch_1_fun/cond/branch_1_fun/hist/"
                     "jit(level_pass)/pallas_call", "fused_level.py:556"),
    "fusion.1": (GROW + "level/cond/branch_1_fun/hist/mul",
                 "frontier2.py:583"),
    "copy.7": (GROW + "level/cond", ""),
    "table_lookup.9": (STEP + "lgbm.grow/shard_map/lgbm.score_update/"
                       "jit(table_lookup)/pallas_call", "fused_level.py:833"),
    "fusion.8": (STEP + "lgbm.valid_apply/jit(add_tree_score)/"
                 "jit(route_rows_to_leaves)/while/body/closed_call/gather",
                 "predict.py:63"),
    "fusion.6": (STEP + "lgbm.eval/auc/jit(argsort)/sort",
                 "metric/__init__.py:465"),
}


def one_chunk() -> list:
    """(event name, start, duration) of one run of the step, 1000 ns from
    t = 1000: a ``while`` that is no leaf around 300 ns of the level
    kernel, 100 ns of grower glue, 50 ns of a ``cond``'s own copy, 100 ns
    of the lookup kernel, 200 ns of the validation walk, 100 ns of the
    metric's sort, 50 ns of a compiler-made ``sort`` and 100 ns idle."""
    t = 1000
    return [(hlo("while.3", "while"), t, 1000),
            (hlo("level_pass.2", "custom-call", "tpu_custom_call"), t, 300),
            (hlo("fusion.1", "fusion"), t + 300, 100),
            (hlo("copy.7", "copy"), t + 400, 50),
            (hlo("table_lookup.9", "custom-call", "tpu_custom_call"),
             t + 450, 100),
            (hlo("fusion.8", "fusion"), t + 550, 200),
            (hlo("fusion.6", "fusion"), t + 750, 100),
            (hlo("sort.2", "sort"), t + 850, 50)]


def xspace_named(devices: int = 1, names: dict = NAMES) -> str:
    """A text proto: ``one_chunk`` on each device and the step run twice
    on the modules line (the second run bounds the window), plus the
    program's thread with its step annotation and drain sections."""
    out = []
    for d in range(devices):
        ops = one_chunk()
        ids = {name: i for i, (name, _, _) in enumerate(ops, 1)}
        events = "\n".join(
            f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {dur * 1000} }}" for n, s, dur in ops)
        meta = []
        for text, i in ids.items():
            inst = text[1:text.index(" ")]
            stats = ""
            if inst in names:
                op_name, source = names[inst]
                stats = f' stats {{ metadata_id: 2 str_value: "{op_name}:" }}'
                if source:
                    stats += (f' stats {{ metadata_id: 3 str_value: '
                              f'"{source}" }}')
            # (hlo() escapes its own quotes for a text proto)
            meta.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                        f'name: "{text}"{stats} }} }}')
        meta.append("  event_metadata { key: 90 value { id: 90 name: "
                    '"jit_step(7)" } }')
        meta += [f'  stat_metadata {{ key: {i} value {{ id: {i} name: '
                 f'"{n}" }} }}' for i, n in ((2, "tf_op"), (3, "source"))]
        out.append(
            f'planes {{ id: {d + 1} name: "/device:TPU:{d}"\n'
            f'  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0\n{events}\n'
            "  }\n"
            '  lines { id: 2 name: "XLA Modules" timestamp_ns: 0\n'
            "    events { metadata_id: 90 offset_ps: 1000000 "
            "duration_ps: 1000000 }\n"
            "    events { metadata_id: 90 offset_ps: 2200000 "
            "duration_ps: 1000000 }\n  }\n" + "\n".join(meta) + "\n}")
    host = [("megastep", 1001, 50), ("GBDT::DrainPending", 1100, 1090),
            ("GBDT::Drain::Fetch", 1100, 910),
            ("GBDT::Drain::HostTree", 2020, 30),
            ("GBDT::Drain::DeviceTree", 2050, 80),
            ("GBDT::Drain::HostTree", 2130, 30),
            ("GBDT::Drain::Replay", 2170, 10)]
    ids = {}
    events = []
    for name, s, dur in host:
        i = ids.setdefault(name, len(ids) + 1)
        stat = (" stats { metadata_id: 1 int64_value: 8 }"
                if name == "megastep" else "")
        events.append(f"    events {{ metadata_id: {i} offset_ps: "
                      f"{s * 1000} duration_ps: {dur * 1000}{stat} }}")
    meta = [f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" '
            "} }" for n, i in ids.items()]
    meta.append('  stat_metadata { key: 1 value { id: 1 name: "step_num" '
                "} }")
    out.append(f'planes {{ id: {devices + 1} name: "/host:CPU"\n'
               '  lines { id: 1 name: "python3" timestamp_ns: 0\n'
               + "\n".join(events) + "\n  }\n" + "\n".join(meta) + "\n}")
    return "\n".join(out) + "\n"
