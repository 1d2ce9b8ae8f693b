"""The plain reference: pandas programs and the row comparison, sharing
nothing with the engine's parser, planner, executor or load path."""
