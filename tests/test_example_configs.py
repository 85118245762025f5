"""The shipped example configs must stay loadable and valid — the same
guarantee the reference's config tests give its example.yamls
(config_test.go:107-133)."""

import os
import re

import pytest

from veneur_tpu.config import read_config, read_proxy_config

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_example_yaml_loads_and_validates():
    cfg = read_config(os.path.join(_ROOT, "example.yaml"))
    cfg.validate()
    cfg.apply_defaults()
    assert cfg.statsd_listen_addresses == ["udp://127.0.0.1:8126"]
    assert cfg.parse_interval() == 10.0
    assert cfg.percentiles == [0.5, 0.75, 0.99]
    assert cfg.digest_storage == "dense"
    # a local instance is one with forward_address set; the example
    # documents both roles but ships as a global
    assert cfg.forward_address == ""


def test_example_host_yaml_loads_and_is_local():
    """The per-host canonical config (the reference's
    example_host.yaml): a LOCAL instance — forward_address set — with
    the documented starting values."""
    cfg = read_config(os.path.join(_ROOT, "example_host.yaml"))
    cfg.validate()
    cfg.apply_defaults()
    assert cfg.forward_address == "http://127.0.0.1:8127"
    assert cfg.parse_interval() == 10.0
    assert cfg.statsd_listen_addresses == ["udp://localhost:8126"]
    assert cfg.aggregates == ["min", "max", "count"]


def test_example_host_yaml_has_no_unknown_keys():
    import yaml

    from veneur_tpu.config import Config

    with open(os.path.join(_ROOT, "example_host.yaml")) as f:
        data = yaml.safe_load(f)
    fields = {f.name for f in
              __import__("dataclasses").fields(Config)}
    unknown = set(data) - fields
    assert not unknown, unknown


def test_example_proxy_yaml_loads():
    cfg = read_proxy_config(os.path.join(_ROOT, "example_proxy.yaml"))
    assert cfg.http_address == "0.0.0.0:8127"
    assert cfg.forward_timeout == "10s"


def test_example_yaml_has_no_unknown_keys():
    """Every key in the example must be a real Config field — a doc'd
    key that the server ignores is exactly the failure mode the dead-key
    audit flagged."""
    import yaml

    from veneur_tpu.config import Config

    with open(os.path.join(_ROOT, "example.yaml")) as f:
        data = yaml.safe_load(f)
    fields = {f.name for f in
              __import__("dataclasses").fields(Config)}
    unknown = set(data) - fields
    assert not unknown, unknown


# -- the documents name only files that exist --------------------------------

_TRACKED_DIRS = ("veneur_tpu", "tests", "benchmark", "docs", "deploy")
_DOCUMENTS = (["README.md", "deploy/README.md",
               ".claude/skills/verify/SKILL.md"]
              + sorted("docs/" + n
                       for n in os.listdir(os.path.join(_ROOT, "docs"))
                       if n.endswith(".md")))
_BACKTICKED = re.compile(r"```.*?```|`[^`\n]+`", re.DOTALL)
_NOT_A_NAME = re.compile(r"[*<>{}$…]|\.\.\.")


@pytest.fixture(scope="module")
def committed_paths():
    """Everything under the tracked directories, as the driver's
    checkout holds it (what a build or a run leaves behind there is in
    directories of its own)."""
    paths = []
    for top in _TRACKED_DIRS + (".claude",):
        for here, dirs, files in os.walk(os.path.join(_ROOT, top)):
            dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
            rel = os.path.relpath(here, _ROOT)
            paths += [f"{rel}/{n}" for n in files]
    return paths


def _named_files(text):
    """Every word in backticks that can name a file of this repository,
    its ``:line`` or ``::name`` cut off: a path that starts with a
    tracked directory, or a name or package-relative path ending in
    ``.py``, ``.cpp``, ``.md`` or ``.json``. Wildcards, placeholders and
    absolute paths (``/root/reference``) name no file of it, and the
    files a run writes (``server.log``, ``report.jsonl``) fall outside
    by that rule."""
    for span in _BACKTICKED.findall(text):
        for word in span.strip("`").split():
            word = word.strip("()[],;.'\"").split(":")[0]
            if (not word or word.startswith("/")
                    or _NOT_A_NAME.search(word)):
                continue
            top, _, rest = word.partition("/")
            if (rest and top in _TRACKED_DIRS) or word.endswith(
                    (".py", ".cpp", ".md", ".json")):
                yield word


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_document_names_only_files_that_exist(document, committed_paths):
    """A document that sends an engineer to a file that is gone (a
    deleted harness, a moved test) fails here, not in their hands. A
    path from the root has to exist as written; a name or a path inside
    the package has to end some committed path."""
    with open(os.path.join(_ROOT, document)) as f:
        named = set(_named_files(f.read()))
    missing = sorted(
        w for w in named
        if not os.path.exists(os.path.join(_ROOT, w))
        and (w.partition("/")[0] in _TRACKED_DIRS
             or not any(p.endswith("/" + w) for p in committed_paths)))
    assert not missing, f"{document} names files that do not exist: {missing}"
