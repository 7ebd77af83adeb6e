"""A cell cut to a CPU test's size: the same configuration family, traffic
and comparison at tiny widths (interpret-mode kernels)."""

import copy
import dataclasses

import harness

SMALL = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128, vocab_size=256)


def smoke_cell(name: str):
    cell = harness.load_cell(name)
    traffic = copy.deepcopy(cell.traffic)
    traffic["codec"]["recon_chunk"] = 512
    return dataclasses.replace(cell, sizes=dict(cell.sizes, **SMALL), traffic=traffic)
