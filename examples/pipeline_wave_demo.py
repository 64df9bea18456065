"""PULSE wave pipeline running on 8 simulated devices: trains a UViT with
the folded-stage executor and shows the live loss + schedule/comm facts.

    PYTHONPATH=src python examples/pipeline_wave_demo.py
"""
from repro.launch.train import main as train_main

print("wave pipeline over 8 simulated host devices (4 stages x DP 2):")
train_main(["--arch", "uvit", "--pipeline", "--devices", "8", "--dp", "2",
            "--steps", "30", "--global-batch", "16",
            "--microbatches", "4", "--lr", "2e-3", "--log-every", "5"])
