"""Train the paper's SNN on the synthetic N-MNIST stand-in and evaluate both
silicon modes (the paper's Fig. 8 experiment, reduced).

Both the noise-free and the *noisy* silicon evaluations run on the fused
macro kernel (MAC -> IMA -> KWN/NLD -> LIF in one Pallas launch per event
sequence): the Fig. 7 IMA error model's per-step Gaussian draws are
generated inside the kernel by the counter PRNG, so noisy accuracy costs
the same single launch as clean.  The serving demo drains the same batched
engine twice — clean and noisy — to show noise-faithful serving.

The KWN cell additionally demonstrates **silicon-in-the-loop fine-tuning**
(the reduced Fig. 8 robustness experiment): after the software pre-train,
``--silicon-steps`` noise-aware QAT steps run *through* the fused kernel
(forward = the serving kernel under the Fig. 7 error model with a fresh
counter seed per step; backward = the surrogate BPTT Pallas kernel), and
the clean/noisy fused accuracies are printed before and after — the point
being that training against the silicon's own noise closes the
clean->noisy gap the software-trained model pays at serving time.

Passing ``--stack W1,W2[,...]`` appends a stacked-KWN cell: the same
software train, then clean + noisy evaluation through the *multi-layer*
fused kernel — all L macro layers chained in one Pallas launch per
sequence, the inter-layer spike tensor never leaving the chip — and the
same serving-engine drain.  (Silicon fine-tuning stays single-layer: the
stacked backward is a roadmap follow-up.)

    PYTHONPATH=src python examples/train_snn_events.py [--steps 150]
        [--silicon-steps 100] [--stack 96,64]
"""

import argparse

import jax

from repro import compile_cache
from repro.core import ima
from repro.data import events as ev_lib
from repro.models import snn
from repro.serve.engine import EventRequest, SNNEventEngine


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--silicon-steps", type=int, default=100,
                    help="noise-aware QAT fine-tune steps through the fused "
                         "kernel (KWN mode; 0 disables the stage)")
    ap.add_argument("--silicon-lr", type=float, default=0.3)
    ap.add_argument("--dataset", default="nmnist",
                    choices=list(ev_lib.DATASETS))
    ap.add_argument("--serve-requests", type=int, default=96,
                    help="event streams pushed through the serving engine")
    ap.add_argument("--stack", default="",
                    help="comma-separated hidden widths for an extra "
                         "stacked-KWN cell (e.g. 96,64); every layer runs "
                         "in one multi-layer fused launch")
    args = ap.parse_args()

    ds = ev_lib.EventDataset(ev_lib.DATASETS[args.dataset])
    dcfg = ev_lib.DATASETS[args.dataset]
    noise_model = ima.IMANoiseModel()

    for mode in ("kwn", "nld"):
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode=mode,
                            k=12 if args.dataset == "dvs_gesture" else 3)
        p, losses = snn.train(cfg, ds, n_steps=args.steps, batch=64)
        acc_n, _ = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(1),
                                n_batches=4, noise=noise_model,
                                fused=True)
        acc_f, tele_f = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(1),
                                     n_batches=4, fused=True)
        print(f"{args.dataset} {mode.upper():3s}: loss "
              f"{losses[0]:.2f}->{losses[-1]:.2f}  "
              f"fused acc {acc_f:.3f}  noisy fused acc {acc_n:.3f}  "
              f"mean ADC steps {tele_f['adc_steps']:.1f}/31  "
              f"LIF updates/step {tele_f['lif_updates']:.0f}/128")

        if mode == "kwn" and args.silicon_steps:
            # Silicon-in-the-loop fine-tune: train against the fused kernel
            # under the Fig. 7 error model (fresh counter seed per step).
            p_ft, ft_losses = snn.train(
                cfg, ds, n_steps=args.silicon_steps, batch=64,
                lr=args.silicon_lr, seed=5, silicon=True,
                noise=noise_model, params=p)
            ft_clean, _ = snn.evaluate(p_ft, cfg, ds, jax.random.PRNGKey(1),
                                       n_batches=4, fused=True)
            ft_noisy, _ = snn.evaluate(p_ft, cfg, ds, jax.random.PRNGKey(1),
                                       n_batches=4, noise=noise_model,
                                       fused=True)
            print(f"  silicon fine-tune ({args.silicon_steps} steps, "
                  f"noise-aware QAT): loss "
                  f"{ft_losses[0]:.3f}->{ft_losses[-1]:.3f}  "
                  f"clean {acc_f:.3f}->{ft_clean:.3f}  "
                  f"noisy {acc_n:.3f}->{ft_noisy:.3f}  "
                  f"(gap {acc_f - acc_n:+.3f} -> "
                  f"{ft_clean - ft_noisy:+.3f})")
            p = p_ft   # serve the silicon-tuned model below

        if mode == "kwn" and args.serve_requests:
            key = jax.random.PRNGKey(7)
            ev, lab = ds.sample(key, args.serve_requests)
            for tag, noise in (("clean", None), ("noisy", noise_model)):
                engine = SNNEventEngine(cfg, p, batch_slots=32, noise=noise)
                for i in range(args.serve_requests):
                    engine.submit(EventRequest(uid=i, events=ev[i],
                                               label=int(lab[i])))
                done = engine.run()
                hits = sum(r.pred == r.label for r in done)
                rep = engine.energy_report(args.dataset)
                print(f"  serve[{tag}]: {len(done)} requests  "
                      f"acc {hits/len(done):.3f}  measured ADC saving "
                      f"{rep['measured_adc_saving']:.2f}  "
                      f"{rep['pj_per_sop']:.2f} pJ/SOP")

    if args.stack:
        widths = tuple(int(w) for w in args.stack.split(","))
        k_top = 12 if args.dataset == "dvs_gesture" else 3
        cfg = snn.SNNConfig(n_in=dcfg.n_in, n_steps=dcfg.n_steps,
                            n_classes=dcfg.n_classes, mode="kwn",
                            hidden_layers=widths,
                            k_layers=(k_top,) * len(widths))
        p, losses = snn.train(cfg, ds, n_steps=args.steps, batch=64)
        acc_f, tele_f = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(1),
                                     n_batches=4, fused=True)
        acc_n, _ = snn.evaluate(p, cfg, ds, jax.random.PRNGKey(1),
                                n_batches=4, noise=noise_model, fused=True)
        layers = "x".join(str(w) for w in widths)
        print(f"{args.dataset} KWN stack {layers} (one fused launch, "
              f"{len(widths)} layers on-chip): loss "
              f"{losses[0]:.2f}->{losses[-1]:.2f}  "
              f"fused acc {acc_f:.3f}  noisy fused acc {acc_n:.3f}  "
              f"skipped blocks {tele_f['skipped_block_ratio']:.2f}")
        if args.serve_requests:
            ev, lab = ds.sample(jax.random.PRNGKey(7), args.serve_requests)
            engine = SNNEventEngine(cfg, p, batch_slots=32)
            for i in range(args.serve_requests):
                engine.submit(EventRequest(uid=i, events=ev[i],
                                           label=int(lab[i])))
            done = engine.run()
            hits = sum(r.pred == r.label for r in done)
            rep = engine.energy_report(args.dataset)
            print(f"  serve[stack]: {len(done)} requests  "
                  f"acc {hits/len(done):.3f}  "
                  f"{rep['pj_per_sop']:.2f} pJ/SOP")


if __name__ == "__main__":
    main()
