"""Container stack reproducibility (VERDICT r3 next #3).

The reference pins every external training component to an exact
commit (container/Dockerfile:16-19 tensorpack @db541e8;
container-optimized/Dockerfile:26-31 mask-rcnn-tensorflow @99dda64 +
cocoapi @6ac4a93), so a rebuild months later trains the same stack.
The TPU images' equivalent is container/constraints.txt: these tests
assert the pins are exact, that every pip install in every image
routes through the constraints file, and that the pinned versions are
THE versions this test suite runs against — the tested stack is the
shipped stack.
"""

import glob
import os
import re
from importlib.metadata import version

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONSTRAINTS = os.path.join(REPO, "container", "constraints.txt")
# glob, not an enumerated list: a future container-*/Dockerfile must
# not silently bypass the every-install-is-constrained invariant
DOCKERFILES = sorted(glob.glob(os.path.join(REPO, "container*",
                                            "Dockerfile")))
assert len(DOCKERFILES) >= 4, DOCKERFILES


def _pins():
    pins = {}
    for line in open(CONSTRAINTS):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, _, ver = line.partition("==")
        pins[name] = ver
    return pins


def test_constraints_are_exact_pins():
    pins = _pins()
    assert len(pins) >= 10
    for name, ver in pins.items():
        assert re.fullmatch(r"[A-Za-z0-9_.-]+", name), name
        # exact PEP440 release (optionally pre/post/dev) — no ranges
        assert re.fullmatch(
            r"\d+(\.\d+)*((a|b|rc)\d+)?(\.post\d+)?(\.dev\d+)?", ver), (
            f"{name} must be pinned to an exact release, got {ver!r}")


def test_every_pip_install_uses_constraints():
    """One unpinned `pip install` line separates 'reproducible
    benchmark' from 'whatever shipped that week' (VERDICT r3 weak #5).
    Every install in every image must route through constraints.txt."""
    for df in DOCKERFILES:
        content = open(df).read()
        # join continuation lines so a multi-line RUN is one statement
        joined = content.replace("\\\n", " ")
        for line in joined.splitlines():
            if "pip install" not in line:
                continue
            for stmt in line.split("&&"):
                if "pip install" in stmt:
                    assert "-c /eksml_tpu/constraints.txt" in stmt, (
                        f"{df}: unconstrained pip install: "
                        f"{stmt.strip()[:120]}")


def test_pins_match_the_tested_environment():
    """The constraints must equal the live versions the suite runs
    against — otherwise 'tests green' says nothing about the image."""
    mismatches = {}
    for name, ver in _pins().items():
        try:
            live = version(name)
        except Exception:  # noqa: BLE001 — not importable here
            continue
        if live != ver:
            mismatches[name] = (ver, live)
    assert not mismatches, (
        f"constraints.txt disagrees with the tested environment "
        f"(pin, live): {mismatches} — update container/constraints.txt")


def test_base_image_tag_is_exact():
    """`python:3.11-slim` floats across patch releases; the base must
    be an exact tag (≙ the reference's DLC base pinned to
    1.15.2-gpu-py36-cu100-ubuntu18.04)."""
    content = open(os.path.join(REPO, "container", "Dockerfile")).read()
    m = re.search(r"^FROM\s+(\S+)", content, re.M)
    assert m, "no FROM in container/Dockerfile"
    assert re.fullmatch(r"python:\d+\.\d+\.\d+-slim", m.group(1)), (
        f"base image must be an exact patch tag, got {m.group(1)}")


def test_constraints_copied_before_install():
    """The COPY of constraints.txt must use the repo-root-relative
    path (the build context is $REPO_ROOT — build_and_push.sh:54) and
    precede the first pip install or the -c reference cannot resolve
    at build time."""
    joined = open(os.path.join(
        REPO, "container", "Dockerfile")).read().replace("\\\n", " ")
    copy_at = joined.find(
        "COPY container/constraints.txt /eksml_tpu/constraints.txt")
    install_at = joined.find("pip install")
    assert 0 <= copy_at < install_at


def test_constraints_regenerate_is_stable():
    """tools/gen_constraints.py output must equal the checked-in file
    (same environment in, same lock out) — the regeneration path the
    header documents cannot drift from what ships."""
    import io
    from contextlib import redirect_stdout

    import tools.gen_constraints as gc

    buf = io.StringIO()
    with redirect_stdout(buf):
        gc.main()
    assert buf.getvalue() == open(CONSTRAINTS).read()


def test_constraints_extras_pinned_through_their_root():
    """ADVICE r4: the closure walk must visit extras-bearing roots
    BEFORE a transitive dep reaches the same package extras-less —
    jax[tpu]'s extras-gated deps (libtpu, requests) must stay pinned
    even when every other root that happens to pull them is removed."""
    import tools.gen_constraints as gc

    roots = [r for r in gc.ROOTS if r[0] not in ("jupyterlab",
                                                 "libtpu")]
    pins = gc.closure(roots)
    assert "requests" in pins, "jax[tpu] extras dep lost by LIFO walk"
    assert "libtpu" in pins, "jax[tpu] extras dep lost by LIFO walk"


def test_image_kind_covers_all_four_dockerfiles():
    """ONE parameterized build script replaces the reference's four
    byte-identical per-directory copies
    (container*/build_tools/build_and_push.sh:25-58): every IMAGE_KIND
    must map to an existing Dockerfile, every container directory must
    be reachable through some kind, and the sourced set_env files must
    exist where the script looks for them."""
    script = os.path.join(REPO, "container", "build_tools",
                          "build_and_push.sh")
    text = open(script).read()

    kind_to_dockerfile = {
        "train": "container/Dockerfile",
        "viz": "container-viz/Dockerfile",
        "optimized": "container-optimized/Dockerfile",
        "optimized-viz": "container-optimized-viz/Dockerfile",
    }
    import re as _re

    case_arms = set(_re.findall(r"^\s*([a-z|-]+)\)", text, _re.M))
    kinds_handled = {k for arm in case_arms for k in arm.split("|")}
    for kind, df in kind_to_dockerfile.items():
        assert kind in kinds_handled, f"IMAGE_KIND={kind} not handled"
        assert f"$REPO_ROOT/{df}" in text, (
            f"{df} not referenced for IMAGE_KIND={kind}")
        assert os.path.exists(os.path.join(REPO, df)), f"{df} missing"
    # unknown kinds fail loudly instead of building the wrong image
    assert "unknown IMAGE_KIND" in text

    # the set_env files the script sources exist at the paths used
    assert os.path.exists(os.path.join(
        REPO, "container", "build_tools", "set_env.sh"))
    assert os.path.exists(os.path.join(
        REPO, "container-optimized", "build_tools", "set_env.sh"))
    assert "container-optimized/build_tools/set_env.sh" in text


def test_derived_images_layer_on_their_bases():
    """viz and optimized layer on the TRAIN image; optimized-viz
    layers on the OPTIMIZED image (reference rebuilds the full stack
    four times; here the heavy jax/libtpu layer is built once)."""
    script = os.path.join(REPO, "container", "build_tools",
                          "build_and_push.sh")
    text = open(script).read()
    assert text.count("--build-arg BASE_IMAGE=") == 3
    # viz + optimized point at the train image; optimized-viz at the
    # optimized image tag
    assert text.count('--build-arg BASE_IMAGE="$TRAIN_BASE"') == 2
    assert ('--build-arg BASE_IMAGE="${REGISTRY}/${IMAGE_NAME}:'
            '${IMAGE_TAG}"') in text
    for d in ("container-viz", "container-optimized",
              "container-optimized-viz"):
        df = open(os.path.join(REPO, d, "Dockerfile")).read()
        assert "ARG BASE_IMAGE" in df, f"{d} missing BASE_IMAGE arg"


def test_container_copies_only_tracked_files(tracked_files):
    """The build context is the checkout: a ``COPY`` of a file the tree
    no longer holds fails the image build, which no other test runs."""
    copied = []
    for df in DOCKERFILES:
        for line in open(df).read().replace("\\\n", " ").splitlines():
            if not line.startswith(("COPY ", "ADD ")):
                continue
            words = [w for w in line.split()[1:]
                     if not w.startswith("--")]
            copied += [(os.path.relpath(df, REPO), src)
                       for src in words[:-1]]
    assert len(copied) >= 6, copied
    missing = [
        (df, src) for df, src in copied
        if src not in tracked_files
        and not any(p.startswith(src.rstrip("/") + "/")
                    for p in tracked_files)]
    assert not missing, missing
