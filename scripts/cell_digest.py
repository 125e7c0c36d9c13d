"""One run of a benchmark cell from a checkout, with the model's digest.

  python3 scripts/cell_digest.py --root <checkout> --tag <name> -- \
      --workload <cell> --seed <n> --seconds 36 --trace 0

Runs ``<checkout>/benchmark/run.py`` in this process with ``lgb.train``
wrapped: after the job, ``dump_model()``'s trees are hashed and written
with the run's arguments to ``chiprun_out/cell_digest/<tag>.json`` (of
the directory the command was started in), the trees themselves beside
it as ``<tag>.trees.json.gz``. Two checkouts that print the same digest
for one seed grew the same model; where the digests differ,
``--compare <a> <b>`` (two tags, no chip) says which fields of the
dumped trees differ, how many values of each and by how much. The
benchmark's own result line goes to standard output as always; nothing
inside the measured window changes (the hash is taken after
``lgb.train`` returns).
"""
import argparse
import gzip
import hashlib
import json
import os
import runpy
import sys


def compare(out_dir: str, a: str, b: str) -> dict:
    """Which fields of two runs' dumped trees differ: per key of the dump
    (``threshold``, ``leaf_value``, ``internal_value``, ...) how many
    values and the largest relative difference of a number."""
    load = lambda tag: json.load(gzip.open(
        os.path.join(out_dir, tag + ".trees.json.gz"), "rt"))
    differ = {}

    def walk(x, y, key):
        same = type(x) is type(y)
        if same and isinstance(x, dict) and x.keys() == y.keys():
            for k in x:
                walk(x[k], y[k], k)
        elif same and isinstance(x, list) and len(x) == len(y):
            for i, j in zip(x, y):
                walk(i, j, key)
        elif x != y:
            d = differ.setdefault(key, {"values": 0, "max_rel_diff": 0.0})
            d["values"] += 1
            if isinstance(x, (int, float)) and isinstance(y, (int, float)):
                d["max_rel_diff"] = max(d["max_rel_diff"],
                                        abs(x - y) / max(abs(x), 1e-30))
    walk(load(a), load(b), "tree_info")
    return {"a": a, "b": b, "differ": differ}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root")
    ap.add_argument("--tag")
    ap.add_argument("--compare", nargs=2, metavar="TAG")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest
    out_dir = os.path.join(os.getcwd(), "chiprun_out", "cell_digest")
    os.makedirs(out_dir, exist_ok=True)
    if args.compare:
        print(json.dumps(compare(out_dir, *args.compare)))
        return
    root = os.path.abspath(args.root)
    bench = os.path.join(root, "benchmark")
    sys.path[:0] = [bench, root]
    import lightgbm_tpu as lgb
    assert os.path.dirname(os.path.dirname(lgb.__file__)) == root
    train = lgb.train

    def digesting(*a, **kw):
        bst = train(*a, **kw)
        trees = bst.dump_model(num_iteration=-1)["tree_info"]
        text = json.dumps(trees, sort_keys=True)
        with gzip.open(os.path.join(out_dir, args.tag + ".trees.json.gz"),
                       "wt") as fh:
            fh.write(text)
        counters = bst.telemetry().get("counters", {})
        with open(os.path.join(out_dir, args.tag + ".json"), "w") as fh:
            json.dump({"tag": args.tag, "root": root, "args": rest,
                       "trees": len(trees),
                       "model_sha256": hashlib.sha256(
                           text.encode()).hexdigest(),
                       "route_counters": {k: v for k, v in counters.items()
                                          if k.startswith(("route.",
                                                           "level."))}},
                      fh)
        return bst
    lgb.train = digesting
    os.chdir(root)
    sys.argv = [os.path.join(bench, "run.py")] + rest
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
