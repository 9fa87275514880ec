"""BENCHMARK.json against the contract's limits, and against the files the
harness finds by name. The checks themselves are functions of a root
directory (manifest_checks.py); here they hold the repo's own manifest."""

import os

import pytest

import manifest_checks as checks

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def manifest():
    return checks.manifest(REPO)


def test_top_level_keys_and_limits():
    checks.check_top_level(REPO)


@pytest.mark.parametrize("metric", checks.all_metrics(REPO), ids=lambda m: m["name"])
def test_metric_entry(metric):
    checks.check_metric_entry(REPO, metric)


def test_names_are_unique_and_setup_s_is_there():
    checks.check_names(REPO)


@pytest.mark.parametrize("config", manifest()["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    checks.check_config_entry_and_file(REPO, config)


@pytest.mark.parametrize("cell", manifest()["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_to_files_that_exist(cell):
    checks.check_workload_resolves_to_files(REPO, cell)


def test_at_most_half_of_the_cells_take_four_chips():
    checks.check_at_most_half_take_four_chips(REPO)


def test_every_layer_metric_file_has_a_manifest_entry():
    checks.check_every_layer_metric_file_has_an_entry(REPO)


def test_files_under_paths_are_named_from_name_characters():
    checks.check_files_are_named_from_name_characters(REPO)
