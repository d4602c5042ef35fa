"""Test harness: 8-device virtual CPU mesh.

The reference has zero automated tests (SURVEY.md §4); its multi-node
path is only exercised on a live cluster.  Here the TPU-world "fake
backend" is XLA's host-platform device-count override: every test sees 8
CPU devices, so mesh/sharding/collective code paths compile and run
without hardware.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

import json

import numpy as np
import pytest


# shared tiny-model KEY=VALUE overrides for subprocess-driven tests —
# canonical list lives in eksml_tpu.config.SMOKE_OVERRIDES
from eksml_tpu import config as config_mod
from eksml_tpu.config import SMOKE_OVERRIDES

TINY_MODEL_OVERRIDES = list(SMOKE_OVERRIDES)

# the one global config tree as the import left it, before any test
_PRISTINE_CONFIG = config_mod.config.to_dict()


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)


@pytest.fixture(autouse=True, scope="module")
def _config_put_back_after_module():
    """Each test module leaves the one global config tree as it found
    it.  A module that reaches ``harness.program_config`` writes a
    cell's overrides into the tree (``MODE_MASK=False`` for the
    Faster-RCNN cell), and modules that read the tree bare
    (``tests/test_config.py``) may follow it on the same worker under
    ``--dist loadfile``."""
    saved, frozen = config_mod.config.to_dict(), config_mod.config._frozen
    yield
    config_mod.config.freeze(False)
    config_mod.config.from_dict(saved)
    config_mod.config.freeze(frozen)


@pytest.fixture(scope="session")
def tracked_files():
    """Repo-relative paths of the files git would commit (tracked, or
    new and not ignored).  A checkout without ``.git`` holds exactly
    those plus what building and testing leave behind, so there every
    file outside dot-directories and caches counts."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if os.path.isdir(os.path.join(root, ".git")):
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard"], cwd=root, check=True,
            capture_output=True, text=True).stdout
        return frozenset(p for p in out.splitlines()
                         if os.path.exists(os.path.join(root, p)))
    found = set()
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if not x.startswith(".")
                   and x not in ("__pycache__", "chiprun_out",
                                 "_archive")]
        found.update(os.path.relpath(os.path.join(d, f), root)
                     for f in files if not f.endswith((".pyc", ".so")))
    return frozenset(found)


@pytest.fixture()
def fresh_config():
    """The global config at its import-time defaults; tests mutate
    freely without leaking.  Reset to the defaults, not to whatever an
    earlier test of the same worker left in the tree (the benchmark's
    ``program_config`` leaves its cell's overrides, e.g.
    ``MODE_MASK=False``): which files share a worker under
    ``--dist loadfile`` changes with their run times."""
    saved = config_mod.config.to_dict()
    config_mod.config.freeze(False)
    config_mod.config.from_dict(_PRISTINE_CONFIG)
    yield config_mod.config
    config_mod.config.freeze(False)
    config_mod.config.from_dict(saved)
    config_mod.config.freeze()


@pytest.fixture()
def mini_coco(tmp_path):
    """Genuine on-disk COCO layout in miniature (JPEGs, polygon
    annotations, the staged-data contract) — shared by the run.sh
    smoke and the notebook-execution e2e."""
    from PIL import Image

    rng = np.random.RandomState(0)
    base = tmp_path / "data"
    cats = [{"id": 1, "name": "person"}, {"id": 18, "name": "dog"}]
    for split, n_img in (("train2017", 6), ("val2017", 2)):
        (base / split).mkdir(parents=True)
        images, anns = [], []
        aid = 1
        for i in range(n_img):
            h, w = int(rng.randint(60, 100)), int(rng.randint(60, 100))
            name = f"{split}_{i:03d}.jpg"
            Image.fromarray(
                rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
            ).save(base / split / name, quality=90)
            iid = 1000 + i if split == "train2017" else 2000 + i
            images.append({"id": iid, "file_name": name,
                           "height": h, "width": w})
            for _ in range(int(rng.randint(1, 4))):
                bw, bh = rng.randint(10, 30, 2)
                x = int(rng.randint(0, w - bw))
                y = int(rng.randint(0, h - bh))
                anns.append({
                    "id": aid, "image_id": iid,
                    "category_id": int(rng.choice([1, 18])),
                    "bbox": [x, y, int(bw), int(bh)],
                    "iscrowd": 0, "area": int(bw * bh),
                    "segmentation": [[x, y, x + int(bw), y,
                                      x + int(bw), y + int(bh),
                                      x, y + int(bh)]],
                })
                aid += 1
        (base / "annotations").mkdir(exist_ok=True)
        with open(base / "annotations" / f"instances_{split}.json",
                  "w") as f:
            json.dump({"images": images, "annotations": anns,
                       "categories": cats}, f)
    return str(base)
