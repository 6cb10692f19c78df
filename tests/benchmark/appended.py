"""What a later PR brings with new files and appended entries only, laid
over a copy of the benchmark: a second configuration (131,072 positions,
a vocabulary of 163,840), a mix of its own whose requests reach 8,192
tokens, one cell with a new suffix, an end-to-end metric for it and its
two per-layer entries on readers that are already there.  The structure
tests run on the committed benchmark and on this copy, so a test that
pins what only the committed cells have fails here first."""
import copy
import json
import os
import shutil

from benchmark import harness

COMMITTED = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELL = "later-config.long-prompt"
CONFIG = {
    "name": "later-config", "reduced": ["num_hidden_layers"],
    "reference": "benchmark.references.gpt",
    "hidden_size": 2048, "num_hidden_layers": 8, "num_attention_heads": 16,
    "intermediate_size": 8192, "vocab_size": 163840,
    "max_position_embeddings": 131072, "tie_word_embeddings": False}
MIX = {
    "kind": "serve",
    "source": {"lengths": "stub: prompts of 2k-8k, short answers",
               "arrivals": "stub: exponential gaps, open loop"},
    "arrivals": {"gaps": "exponential", "rate_per_s": 1.0},
    "prompt_tokens": {"dist": "lognormal", "mean": 4000, "sigma": 0.5,
                      "min": 2048, "max": 8000},
    "output_tokens": {"dist": "lognormal", "mean": 48, "sigma": 0.5,
                      "min": 16, "max": 192},
    "lead_s": 5.0, "base_seed": 7,
    "engine": {"num_blocks": 4200, "block_size": 16, "max_running": 8,
               "prefill_chunk": 512},
    "limits": {"served_logit_gap": 0.25}}


def appended():
    """The committed BENCHMARK.json with every new entry at the END of
    its list, and no entry that is there touched."""
    bench = copy.deepcopy(COMMITTED)
    bench["configs"].append({
        "name": CONFIG["name"], "source": "https://example.org/later-config",
        "file": "benchmark/configs/later-config.json",
        "reduced": CONFIG["reduced"], "why": "stub of a later configuration"})
    bench["workloads"].append({
        "name": CELL, "config": CONFIG["name"], "traffic": "long-prompt",
        "chips": 1, "why": "stub: prompts of 2k-8k at 1 req/s"})
    bench["end_to_end"].append({
        "name": "ttft_p95_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": [CELL]})
    for name, better, source, layer in (
            ("serve_mfu.long", "higher", "program_counter",
             "whole serving step"),
            ("device_idle_share.long", "lower", "device_trace", "device")):
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": layer, "moves": "ttft_p95_ms", "workloads": [CELL]})
    return bench


BENCHES = {"committed": COMMITTED, "appended": appended()}


def write(dest):
    """The copy under `dest`: the committed data files, the new ones
    beside them, and the appended BENCHMARK.json.  Returns `dest`."""
    for folder in ("configs", "traffic"):
        shutil.copytree(os.path.join(harness.HERE, folder),
                        os.path.join(dest, "benchmark", folder))
    for rel, body in (
            ("BENCHMARK.json", BENCHES["appended"]),
            ("benchmark/configs/later-config.json", CONFIG),
            ("benchmark/traffic/long-prompt.json", MIX)):
        with open(os.path.join(dest, rel), "w") as f:
            json.dump(body, f, indent=1)
    return str(dest)
