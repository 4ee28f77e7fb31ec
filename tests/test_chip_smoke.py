"""chip_smoke.py refuses to run anywhere but on a TPU: on the CPU backend
its device check exits non-zero and no result line is printed."""
import importlib.util
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("entry", ["require_tpu", "main"])
def test_device_check_exits_off_tpu(chip_smoke, capsys, entry):
    assert jax.devices()[0].platform != "tpu"
    call = getattr(chip_smoke, entry)
    with pytest.raises(SystemExit) as exc:
        call() if entry == "require_tpu" else call([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out
