"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``
and turns it into the GRPO run the program is given, plus the task's
reward.

A mix fixes the batch (prompts x group), prompt and response lengths, the
placement mode, the sampling temperature and the trainer's
hyperparameters.  Prompts come from the program's synthetic
arithmetic dataset seeded by ``--seed``; every seed gives the same sizes.

The reward is the benchmark's task, applied where the workflow scores a
rollout: random weights never solve the arithmetic task, so its own
rule-based reward scores every response alike, every group-relative
advantage is 0 and the actor's policy loss and gradient vanish.  The
reward here scores random responses unevenly, so the actor trains on real
gradients.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
PAD, EOS = 0, 2  # the program's token ids for padding and end of sequence


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def response_mask(tokens: np.ndarray, prompt_len: int) -> np.ndarray:
    mask = np.zeros(tokens.shape, np.float32)
    mask[:, prompt_len:] = tokens[:, prompt_len:] != PAD
    return mask


def upper_half_reward(tokens, prompt_len, vocab_size):
    """The task's reward: the share of response tokens (up to EOS) in the
    upper half of the vocabulary."""
    out = np.zeros((tokens.shape[0],), np.float64)
    for i, row in enumerate(tokens[:, prompt_len:]):
        stop = np.flatnonzero(row == EOS)
        row = row[: stop[0]] if stop.size else row
        out[i] = np.mean(row >= vocab_size // 2) if row.size else 0.0
    return out



def group_advantages(rewards: np.ndarray, group: int) -> np.ndarray:
    """GRPO: (r - group mean) / (group std + 1e-6), statistics in float64."""
    g = rewards.reshape(-1, group).astype(np.float64)
    adv = (g - g.mean(1, keepdims=True)) / (g.std(1, keepdims=True) + 1e-6)
    return adv.reshape(-1).astype(np.float32)


def score(tokens: np.ndarray, mix: dict, vocab_size: int):
    """(rewards, loss mask, per-token advantages) of a rollout batch."""
    P = mix["prompt_len"]
    rewards = upper_half_reward(tokens, P, vocab_size).astype(np.float32)
    mask = response_mask(tokens, P)
    adv = group_advantages(rewards, mix["group"])
    return rewards, mask, adv[:, None] * mask


def reward_stage(mix: dict, vocab_size: int):
    """The workflow's reward task: ``fn(worker, chunk) -> chunk``."""
    def run(_worker, chunk):
        rewards, mask, adv = score(np.asarray(chunk["tokens"]), mix,
                                   vocab_size)
        out = dict(chunk)
        out.update(rewards=rewards, loss_mask=mask, advantages=adv)
        return out
    return run


def iteration_flops(ref, c: dict, mix: dict) -> dict:
    """Model FLOPs one iteration requires, by stage, from the
    configuration's ``token_flops``: the rollout prefills each distinct
    prompt once and decodes every response token but the last (it is
    never fed back); the inference pass and the actor's forward cover
    every position, and the actor's backward costs twice its forward."""
    B, g = mix["batch"], mix["group"]
    P, N = mix["prompt_len"], mix["max_new_tokens"]
    S = P + N
    prefill = (B // g) * P * ref.token_flops(c, (P + 1) / 2, seq=P)
    decode = B * (N - 1) * ref.token_flops(c, P + N / 2)
    full = B * S * ref.token_flops(c, (S + 1) / 2, seq=S)
    return {"rollout": prefill + decode, "inference": full,
            "actor": 3 * full}
