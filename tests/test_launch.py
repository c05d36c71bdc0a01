"""Launch layer: HLO collective parsing + mini dry-run on a 4x4 fake mesh.

A fast structural test that the cell programs lower+compile with their
shardings on a small mesh, in a subprocess so the fake device count never
leaks into other tests.
"""
import json
import os
import subprocess
import sys

from repro.launch.hlo_stats import collective_bytes, collective_schedule


SAMPLE_HLO = """
  %ar = f32[128,1024]{1,0} all-reduce(f32[128,1024]{1,0} %x), replica_groups={}
  %ag.1 = bf16[32,4096]{1,0} all-gather(bf16[32,256]{1,0} %y), dimensions={1}
  %tup = (s32[64]{0}, s32[64]{0}) all-to-all(s32[64]{0} %a, s32[64]{0} %b)
  %cp = u8[16,16]{1,0} collective-permute(u8[16,16]{1,0} %z)
  %rs = f32[8,8]{1,0} reduce-scatter(f32[64,8]{1,0} %w), dimensions={0}
"""


def test_collective_bytes_parses_all_kinds():
    out = collective_bytes(SAMPLE_HLO)
    assert out["count"] == 5
    assert out["all-reduce"] == 128 * 1024 * 4
    assert out["all-gather"] == 32 * 4096 * 2
    assert out["all-to-all"] == 64 * 4 * 2          # tuple of two s32[64]
    assert out["collective-permute"] == 16 * 16 * 1
    assert out["reduce-scatter"] == 8 * 8 * 4
    assert out["total"] == sum(v for k, v in out.items()
                               if k not in ("total", "count"))


def test_collective_schedule_order():
    sched = collective_schedule(SAMPLE_HLO)
    assert sched[0].startswith("all-reduce")
    assert sched[1].startswith("all-gather")


_MINI = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, sys
sys.path.insert(0, "__SRC__")
import jax
from jax.sharding import NamedSharding
from repro.configs import get_arch
from repro.launch.hlo_stats import collective_bytes

mesh = jax.make_mesh((4, 4), ("data", "model"))
results = {}
for aid, sid in [("qwen2-1.5b", "train_4k"), ("gat-cora", "molecule"),
                 ("bert4rec", "train_batch")]:
    prog = get_arch(aid).build(sid, multipod=False, reduced=True)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), prog.arg_specs,
                      is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    with mesh:
        compiled = jax.jit(prog.step_fn, in_shardings=sh).lower(
            *prog.abstract_args).compile()
    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    results[f"{aid}/{sid}"] = dict(
        temp=int(getattr(mem, "temp_size_in_bytes", 0)),
        coll=int(coll["total"]), n_coll=int(coll["count"]))
print(json.dumps(results))
"""


def test_mini_dryrun_cells_compile_sharded():
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    code = _MINI.replace("__SRC__", src)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(res) == 3
    # data+model sharded programs must actually communicate
    assert res["qwen2-1.5b/train_4k"]["n_coll"] > 0
