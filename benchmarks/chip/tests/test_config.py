"""The configuration files state the deployment the program runs: the
layer shapes of the program's ``workloads/cnn.py`` (the paper's, but for
ResNet18's conv2_x, see the files' ``assumed``), design grid and
technology constants."""
import json

import numpy as np

from conftest import BENCH


def _cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_configs_hold_the_programs_published_shapes():
    from repro.core import space
    from repro.imc.tech import TECH
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload

    for name in ("paper_cnn4", "paper_cnn4.mesh4"):
        cfg = _cfg(name)
        assert list(cfg["workloads"]) == list(PAPER_WORKLOADS)
        for w in PAPER_WORKLOADS:
            assert [tuple(l) for l in cfg["workloads"][w]] == \
                [tuple(l) for l in cnn_workload(w)]
        for f in space.FIELDS:
            np.testing.assert_array_equal(
                np.asarray(cfg["design_space"][f], np.float32), space.SPACE[f])
        assert cfg["tech"] == TECH._asdict()
        size = np.prod([len(v) for v in cfg["design_space"].values()])
        assert size == 19_200_000


def test_mesh_config_is_the_one_chip_deployment_times_four():
    one, four = _cfg("paper_cnn4"), _cfg("paper_cnn4.mesh4")
    assert four["service"]["max_slots"] == 4 * one["service"]["max_slots"]
    assert four["layout"]["mesh"] == {"search": 4, "data": 1}
    for k in ("workloads", "design_space", "tech", "ga", "area_mm2"):
        assert one[k] == four[k]
