"""The few facts the benchmark reads from inside the system under test.

Everything else goes through the public entry points (`lgb.Dataset`,
`lgb.Booster(...).update()`, `Booster.predict`, `Booster(model_str=...)`,
`model_to_string`).  These reads reach past them, so each one answers None
where the program no longer has the name.  A check that rests on such a
fact then says "not observable" and the run is not `correct`
(`harness.correct`): a PR that renames what is read here needs a
`benchmark` PR to follow it, and until then no run passes unseen.
"""


def hist_impl(booster):
    """The histogram implementation the learner resolved (`pallas2`,
    `xla`, ...)."""
    try:
        return str(booster._driver.learner.params.hist_impl)
    except AttributeError:
        return None


def ingest_on_device(dataset):
    """Whether `Dataset.construct` binned on the device."""
    try:
        return dataset._inner.device_ingest_bins() is not None
    except AttributeError:
        return None


def bins_shard_devices(booster):
    """On how many devices the learner keeps shards of the binned table."""
    try:
        shards = booster._driver.learner.bins_t.addressable_shards
        return len({s.device for s in shards})
    except AttributeError:
        return None


def counter_total(name: str):
    """A counter of the program's registry, summed over its labels."""
    try:
        from lightgbm_tpu import obs

        snap = obs.REGISTRY.snapshot()
    except (ImportError, AttributeError):
        return None
    return float(sum(v for k, v in snap.items()
                     if k.split("{")[0] == name and not isinstance(v, dict)))


def no_oom_so_far():
    """True when the process has counted no device out-of-memory event and
    no step down the program's degradation ladder."""
    parts = [counter_total("lgbm_oom_events_total"),
             counter_total("lgbm_oom_ladder_steps_total")]
    return None if None in parts else sum(parts) == 0


def ledger():
    """The program's compile ledger, switched on; None where it is gone."""
    try:
        from lightgbm_tpu.utils.compile_ledger import LEDGER

        LEDGER.enable()
        return LEDGER
    except (ImportError, AttributeError):
        return None


def ledger_programs(site: str):
    """Programs the ledger recorded at one jit site."""
    led = ledger()
    try:
        return None if led is None else int(led.n_programs(site))
    except (AttributeError, TypeError):
        return None
