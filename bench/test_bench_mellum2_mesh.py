"""mellum2.silo4 at a test's size over a 4-device `data` mesh on the CPU
(one silo a device, as on the chips): the harness lays the silos by
rows, the reference computes each silo's gradient on the device that
holds it, and the sharded program reads `correct` against it. The
devices are made per subprocess (`conftest.fake_device_env`)."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests"))

from conftest import fake_device_env  # noqa: E402

SCRIPT = textwrap.dedent("""
    import json
    from bench.test_bench_mellum2 import SmallSpec, one_run

    SmallSpec.chips = 4
    res = one_run(SmallSpec())
    print(json.dumps({"correct": res["correct"], "checks": res["checks"],
                      "rounds": res["window"]["checked_call_rounds"]}))
""")


def test_the_sharded_program_is_correct_on_four_devices():
    env = fake_device_env(4)
    env["PYTHONPATH"] = os.pathsep.join([env["PYTHONPATH"], ROOT])
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["rounds"] == 3
