"""Single front door for the launchers: ``python -m repro <cmd> ...``.

Each subcommand forwards argv to the matching ``repro.launch.*`` module,
so ``python -m repro calibrate --arch ...`` and
``python -m repro.launch.calibrate --arch ...`` are the same program.
"""
import sys

COMMANDS = {
    "calibrate": ("repro.launch.calibrate", "search a QuantPolicy from "
                  "calibration activations"),
    "serve": ("repro.launch.serve", "offline packing + batched decode"),
    "train": ("repro.launch.train", "train-loop entry"),
    "dryrun": ("repro.launch.dryrun", "compile-only cost readout"),
    "breakdown": ("repro.launch.breakdown", "per-instruction cost tables"),
}


def main():
    argv = sys.argv[1:]
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: python -m repro <command> [args]\n\ncommands:")
        for name, (_, desc) in COMMANDS.items():
            print(f"  {name:10} {desc}")
        raise SystemExit(0 if argv else 2)
    cmd = argv[0]
    if cmd not in COMMANDS:
        print(f"unknown command {cmd!r} (expected one of "
              f"{', '.join(COMMANDS)})", file=sys.stderr)
        raise SystemExit(2)
    mod_name = COMMANDS[cmd][0]
    import importlib

    from repro.jax_setup import configure_jax

    configure_jax()

    mod = importlib.import_module(mod_name)
    sys.argv = [f"python -m {mod_name}"] + argv[1:]
    mod.main()


if __name__ == "__main__":
    main()
