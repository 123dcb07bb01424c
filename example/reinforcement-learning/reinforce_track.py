"""REINFORCE policy gradient on an episodic toy environment (reference:
example/reinforcement-learning/ — policy/actor-critic training loops
(a3c/, parallel_actor_critic/) against gym Atari; the algorithmic core is
return-weighted log-likelihood ascent on on-policy rollouts).

Zero-egress version: a 1-D "track" of length 9.  Each episode the agent
starts in the middle and a target appears uniformly at either end; state
= one-hot(agent) ++ one-hot(target); actions = {left, right}; reward 1.0
on reaching the target within the step budget, else 0, discounted by
gamma per step.  Optimal policy = walk toward the target (avg return
about 0.66 at gamma=0.9); a random policy earns about 0.18.

The update is textbook REINFORCE with a moving-average baseline: rollouts
are collected with numpy sampling from the policy's action distribution
(eager forward per env step), then ONE batched autograd pass scores
-log pi(a_t|s_t) * (G_t - b) over every step of every episode — the
gather of per-action log-probs trains through the tape.

Run (CPU smoke):  JAX_PLATFORMS=cpu python example/reinforcement-learning/reinforce_track.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn

TRACK = 9
START = TRACK // 2
MAX_STEPS = 8
GAMMA = 0.9


def encode(pos, target):
    s = np.zeros(2 * TRACK, np.float32)
    s[pos] = 1.0
    s[TRACK + target] = 1.0
    return s


def rollout(net, rng, greedy=False):
    """One episode; returns (states, actions, returns, total_reward)."""
    target = rng.choice([0, TRACK - 1])
    pos = START
    states, actions, rewards = [], [], []
    for _ in range(MAX_STEPS):
        s = encode(pos, target)
        probs = nd.softmax(net(nd.array(s[None]))).asnumpy()[0]
        a = int(probs.argmax()) if greedy else int(
            rng.choice(2, p=probs / probs.sum()))
        pos = max(0, min(TRACK - 1, pos + (1 if a == 1 else -1)))
        states.append(s)
        actions.append(a)
        done = pos == target
        rewards.append(1.0 if done else 0.0)
        if done:
            break
    G, returns = 0.0, []
    for r in reversed(rewards):
        G = r + GAMMA * G
        returns.append(G)
    returns.reverse()
    return states, actions, returns, returns[0] if returns else 0.0


def avg_return(net, rng, episodes, greedy=True):
    return float(np.mean([rollout(net, rng, greedy=greedy)[3]
                          for _ in range(episodes)]))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--updates", type=int, default=150)
    ap.add_argument("--episodes-per-update", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args(argv)

    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(32, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    rng = np.random.RandomState(0)

    ret0 = avg_return(net, np.random.RandomState(99), 40)
    baseline = 0.0
    for upd in range(args.updates):
        all_s, all_a, all_g = [], [], []
        for _ in range(args.episodes_per_update):
            s, a, g, _ = rollout(net, rng)
            all_s += s
            all_a += a
            all_g += g
        sb = nd.array(np.stack(all_s))
        ab = nd.array(np.array(all_a, np.int32))
        adv = np.array(all_g, np.float32) - baseline
        baseline = 0.9 * baseline + 0.1 * float(np.mean(all_g))
        with autograd.record():
            logp = nd.log_softmax(net(sb))
            chosen = nd.pick(logp, ab, axis=1)
            loss = -(chosen * nd.array(adv)).mean()
        loss.backward()
        trainer.step(1)
        if upd % 50 == 0:
            print("update %d avg return %.3f" % (
                upd, float(np.mean(all_g))), flush=True)

    ret = avg_return(net, np.random.RandomState(99), 40)
    print("greedy avg return: %.3f (untrained %.3f)" % (ret, ret0))
    return ret0, ret


if __name__ == "__main__":
    main()
