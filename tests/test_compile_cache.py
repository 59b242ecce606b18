"""Placement rule of the persistent compilation cache (repro.compile_cache).

Only the path decision is exercised: the suite never turns the cache on.
"""

import os

from repro import compile_cache


def test_environment_directory_is_left_to_jax():
    env = {compile_cache.ENV_VAR: "/somewhere/else"}
    assert compile_cache.directory_to_set(env) is None


def test_default_is_one_fixed_path_in_the_checkout():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for env in ({}, {compile_cache.ENV_VAR: ""}):
        assert compile_cache.directory_to_set(env) == os.path.join(
            root, ".jax_cache")


def test_default_path_is_gitignored():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, ".gitignore")) as f:
        ignored = {line.strip().rstrip("/") for line in f}
    assert os.path.basename(compile_cache.REPO_CACHE_DIR) in ignored
