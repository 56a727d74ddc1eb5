"""EDEN-style BER autopilot end to end (README §Autopilot).

Three layers, one story:

  1. **campaign** — group the state tree by path regex (here: FFN weights
     vs the KV cache), sweep a ladder of DRAM refresh points, and measure
     each group's quality degradation in isolation (injected episodes
     teacher-forced against the clean trajectory);
  2. **frontier** — pick the most aggressive refresh each group tolerates
     within one quality budget; a group that fails everywhere collapses to
     an exact-ECC island.  The solver emits the per-region refresh map, a
     concrete `RuleSet`, the expected-fault rates, and the byte-weighted
     energy saving;
  3. **guard** — deploy those expectations online: the serving engine (or
     train loop) watches per-rule fault counters per window and tightens a
     drifting group's rule with hysteresis — stricter detection first,
     exact-ECC demotion second.

Run:  PYTHONPATH=src python examples/autopilot.py
"""
import dataclasses

from repro.autopilot import run_campaign, solve_frontier
from repro.configs import get_preset
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    # -- 1. the profiling campaign ---------------------------------------
    # the transformer preset: a tiny qwen2 with two region groups.  Keep
    # the sweep short for the demo — two refresh points, six decode steps.
    preset = get_preset("transformer", steps=6)
    preset = dataclasses.replace(
        preset,
        campaign=dataclasses.replace(
            preset.campaign, refresh_points=(1.0, 2.0)
        ),
    )
    print(f"profiling {preset.name!r}: "
          f"{[g.name for g in preset.campaign.groups]} x "
          f"{list(preset.campaign.refresh_points)} s refresh")
    profile = run_campaign(preset.build_model(), preset.campaign)
    for c in profile.cells:
        print(f"  {c.group:<12} refresh={c.refresh_s:>5.2f}s "
              f"ber={c.ber:.0e} quality={c.quality:.3f} "
              f"flips={c.flips} saving={c.energy_saving:.3f}")

    # -- 2. the frontier solve -------------------------------------------
    frontier = solve_frontier(profile, budget=preset.budget)
    print(f"\nbudget {preset.budget}: per-group assignment")
    for a in sorted(frontier.assignments, key=lambda a: a.group):
        tag = "EXACT ISLAND" if a.collapsed else f"{a.refresh_s:.2f}s"
        print(f"  {a.group:<12} -> {tag:<12} quality={a.quality:.3f} "
              f"expected_faults/step={a.expected_faults_per_step:.2f}")
    print(f"byte-weighted energy saving: {frontier.energy_saving:.3f}")

    # the artifacts are deployable objects, not a report: a refresh map,
    # a RuleSet, and the guard's expected-rate table
    print(f"refresh map: {frontier.refresh_map()}")
    print(f"rules: {[(p, r.label, r.exact) for p, r in frontier.ruleset().entries]}")
    auto = frontier.autopilot()
    print(f"guard expectations: {auto.expected}")

    # -- 3. the online guard ---------------------------------------------
    # serve with the solved ruleset, but simulate MORE faults than the
    # profile promised (a drifting DRAM module): the guard notices the
    # excess within a few windows and tightens the drifting group's rule.
    import jax

    from repro.models import build_model
    from repro.runtime import ApproxConfig
    from repro.serving import Engine, ServingConfig

    arch = dataclasses.replace(
        preset.arch,
        repair=ApproxConfig(mode="memory", rules=frontier.ruleset()),
    )
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(0))
    cfg = ServingConfig(
        page_size=4, n_pages=16, max_batch=2, max_pages_per_request=8,
        repair="page", ber=2e-3, seed=0,       # ~100x the profiled BER
        paged_decode="off",   # gathered path: repairs land in rule counters
        # short windows + no slack so the drift shows within one request
        autopilot=dataclasses.replace(auto, window=2, patience=1, floor=0.0),
    )
    eng = Engine(model, params, cfg)
    eng.add_request(list(range(1, 9)), max_new=8)
    eng.run()
    print(f"\nserved under drift: autopilot_trips="
          f"{eng.metrics()['autopilot_trips']}")
    for trip in eng.guard.trips:
        print(f"  tightened {trip['label']!r}: {trip['action']} "
              f"(observed {trip['observed']} faults vs "
              f"threshold {trip['threshold']:.1f} in window {trip['window']})")


if __name__ == "__main__":
    main()
